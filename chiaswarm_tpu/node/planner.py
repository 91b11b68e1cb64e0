"""swarmplan (ISSUE 19): the capacity-model-driven fleet autoscaler.

The hive has exported its data plane for three PRs — per-worker metric
snapshots at ``GET /api/fleet`` plus the observed-arrival EWMA (PR 13),
the measured capacity model (PR 9: jobs/s/chip under the offered
workload mix), and a crash-safe journal with exactly-once settlement
across epochs (PR 14/17). This module closes the loop: a hive-side
:class:`FleetPlanner` that, on each planning tick, folds those inputs
into (a) a **target worker count** and (b) a **per-worker model
placement plan**, with the control-theory hygiene a production loop
needs — EWMA smoothing of the demand signal, a hysteresis deadband,
scale-up/scale-down cooldowns, and hard min/max fleet bounds.

Actuation deliberately rides contracts that already exist instead of
inventing a process manager:

- **scale-up** is a *request*: the harness's worker-factory seam spawns
  the workers (``loadgen.run_load(autoscale=...)``); a real deployment's
  supervisor polls ``GET /api/plan`` and starts that many nodes.
- **scale-down** is a *graceful drain*, never the kill path: the victim
  gets ``request_stop()`` (finish in-flight, upload, exit) while
  ``expire_worker()`` preempts its leases so mid-lane jobs redeliver —
  with their journaled checkpoints — to survivors (resume_step >= 1;
  the victim's own racing upload dedupes, exactly-once holds).
- **placement** is a *hint*: the plan's per-worker model lists ride
  heartbeat acks (``ack["placement"]``), and the worker's residency
  ledger warms hinted models on idle polls before traffic shifts — the
  fleet-level generalization of the PR-8 prefetch ranking, driven by
  the same ``UserPopulation`` model affinity.

Every actuating decision is journaled (a ``plan`` HiveJournal
transition plus a flight note on the ``fleet-planner`` pseudo record),
so a recovered hive replays the planner's *intent*: a fresh planner
attached after recovery seeds its cooldown clocks and placement from
``hive.last_plan`` and does not double-actuate the decision the dead
process already made.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import time
from typing import Any, Callable

from chiaswarm_tpu.obs import metrics as obs_metrics

log = logging.getLogger(__name__)

#: the flight-record id every planner decision notes onto — one pseudo
#: record per hive holding the decision timeline (FlightRecorder.note
#: auto-opens it; verify() only audits the job ids it is given, so the
#: pseudo record never trips settlement audits)
PLAN_FLIGHT_ID = "fleet-planner"

# pre-seed the planner families on the GLOBAL registry at import
# (ISSUE 6 convention, asserted by tests/test_obs.py): a dashboard
# scraping /metrics sees zeros before the first planning tick
_TARGET = obs_metrics.planner_target_workers_gauge()
_ACTUAL = obs_metrics.planner_actual_workers_gauge()
_DECISIONS = obs_metrics.planner_decisions_counter()
_MOVES = obs_metrics.planner_placement_moves_counter()
_WORKER_HOURS = obs_metrics.planner_worker_hours_counter()
_TARGET.set(0)
_ACTUAL.set(0)
for _direction in obs_metrics.PLANNER_DIRECTIONS:
    for _reason in obs_metrics.PLANNER_REASONS:
        _DECISIONS.inc(0, direction=_direction, reason=_reason)
_MOVES.inc(0)
_WORKER_HOURS.inc(0)


@dataclasses.dataclass(frozen=True)
class PlannerConfig:
    """The autoscaler's knobs (README "Autoscaling" operator guide).

    ``capacity_jobs_s_per_worker`` is the PRIOR — the PR-9 capacity
    model's jobs/s/worker under the expected mix. The planner refines it
    online from observed settle throughput whenever the fleet is
    provably saturated (hive-side backlog > 0), so a wrong prior
    converges instead of oscillating."""

    min_workers: int = 1
    max_workers: int = 8
    #: plan to run workers at this fraction of measured capacity —
    #: the headroom that absorbs arrival noise between ticks
    target_utilization: float = 0.65
    #: time constant of the demand EWMA (seconds): the planner's view
    #: of the arrival rate moves on this horizon, not per-tick noise
    smoothing_window_s: float = 10.0
    #: fractional deadband around the current size — the raw target
    #: must leave ``actual x (1 +/- hysteresis)`` before actuating
    hysteresis: float = 0.2
    cooldown_up_s: float = 1.0
    cooldown_down_s: float = 5.0
    #: drain any hive-side backlog within this horizon (seconds); the
    #: backlog term is what makes a spike visible before the arrival
    #: EWMA has fully caught up
    backlog_drain_s: float = 5.0
    capacity_jobs_s_per_worker: float = 4.0
    #: blend factor for online capacity refinement (EWMA over
    #: saturated-throughput samples)
    capacity_alpha: float = 0.3
    #: how many workers the hottest model may replicate onto (scaled
    #: by its demand share; every observed model keeps >= 1 home)
    replicate_max: int = 3


class FleetPlanner:
    """One planning loop bound to one hive (or federated front).

    ``tick()`` is pure observation + decision: it never spawns or
    stops anything itself. The caller (the harness's autoscale drive,
    or a real supervisor consuming ``GET /api/plan``) actuates the
    returned decision through the seams named in the module docstring.
    Attaching the planner publishes it on the hive: ``GET /api/plan``
    starts serving and heartbeat acks start carrying placement hints.
    """

    def __init__(self, hive: Any, config: PlannerConfig | None = None,
                 *, clock: Callable[[], float] | None = None,
                 metrics_registry: Any = None) -> None:
        self.hive = hive
        self.config = config or PlannerConfig()
        # a federated front plans fleet-wide over the merged
        # fleet_snapshot; its record_plan/last_plan delegate to the
        # CURRENT shard 0 (the same convention the front's merged read
        # views follow) — bind the front, not the shard object, so a
        # shard-0 kill/restart cycle never strands the planner's
        # journal seam on a dead hive
        shards = getattr(hive, "shards", None)
        self._journal_hive = hive
        self._clock = (clock if clock is not None
                       else getattr(hive, "_clock", time.monotonic))
        reg = (metrics_registry if metrics_registry is not None
               else getattr(hive, "metrics", None))
        if reg is not None:
            self._m_target = obs_metrics.planner_target_workers_gauge(reg)
            self._m_actual = obs_metrics.planner_actual_workers_gauge(reg)
            self._m_decisions = obs_metrics.planner_decisions_counter(reg)
            self._m_moves = obs_metrics.planner_placement_moves_counter(
                reg)
            self._m_hours = obs_metrics.planner_worker_hours_counter(reg)
            self._m_target.set(0)
            self._m_actual.set(0)
            for direction in obs_metrics.PLANNER_DIRECTIONS:
                for reason in obs_metrics.PLANNER_REASONS:
                    self._m_decisions.inc(0, direction=direction,
                                          reason=reason)
            self._m_moves.inc(0)
            self._m_hours.inc(0)
        else:
            self._m_target, self._m_actual = _TARGET, _ACTUAL
            self._m_decisions, self._m_moves = _DECISIONS, _MOVES
            self._m_hours = _WORKER_HOURS
        self._demand: float | None = None
        self._last_tick: float | None = None
        self._capacity = float(self.config.capacity_jobs_s_per_worker)
        self._throughput_anchor: tuple[float, int, int, int] | None = None
        self._arrival_anchor: tuple[float, int] | None = None
        self._last_up: float = float("-inf")
        self._last_down: float = float("-inf")
        # workers this planner has already decided to drain: excluded
        # from the live view (and from re-selection) until they leave
        # the fleet snapshot, so one slow drain is never re-issued
        # tick after tick while blocking OTHER scale-down decisions
        self._draining: dict[str, float] = {}
        self._placement: dict[str, tuple[str, ...]] = {}
        self.last_decision: dict[str, Any] | None = None
        self.ticks = 0
        # recovery seam (the no-double-actuation contract): a journaled
        # hive replays its last plan into ``hive.last_plan``; seeding
        # the cooldown clocks and placement from it means a planner
        # re-attached after a crash treats the dead process's decision
        # as its own recent one instead of re-issuing it
        recovered = getattr(self._journal_hive, "last_plan", None)
        if isinstance(recovered, dict):
            at = float(recovered.get("at_s") or self._clock())
            direction = str(recovered.get("direction") or "hold")
            if direction == "up":
                self._last_up = at
            elif direction == "down":
                self._last_down = at
            placement = recovered.get("placement") or {}
            self._placement = {str(w): tuple(str(m) for m in models)
                               for w, models in placement.items()}
            for name in recovered.get("drain") or ():
                self._draining[str(name)] = at
            if recovered.get("demand_jobs_s") is not None:
                self._demand = float(recovered["demand_jobs_s"])
            if recovered.get("capacity_jobs_s_per_worker"):
                self._capacity = float(
                    recovered["capacity_jobs_s_per_worker"])
            self.last_decision = dict(recovered)
            log.info("planner seeded from journaled plan (direction=%s "
                     "at t=%.3f): cooldowns inherited, no re-actuation",
                     direction, at)
        # publish: /api/plan serves, heartbeat acks carry hints. A
        # federated front publishes on every shard too — shard
        # heartbeat acks are where the workers actually listen.
        hive.planner = self
        for shard in shards or ():
            shard.planner = self

    # ---- observation ---------------------------------------------------

    def _smooth_demand(self, observed: float, now: float) -> float:
        if self._demand is None or self._last_tick is None:
            self._demand = float(observed)
        else:
            dt = max(1e-6, now - self._last_tick)
            alpha = 1.0 - math.exp(-dt / max(1e-6,
                                             self.config.smoothing_window_s))
            self._demand += alpha * (float(observed) - self._demand)
        return self._demand

    def _observe_arrivals(self, agg: dict[str, Any], now: float) -> float:
        """The demand sample for this tick: the hive's own arrival
        EWMA rides a 30 s horizon (a dashboard quantity), which badly
        underestimates a ramp that is seconds old — so the planner also
        differentiates the hive's monotone settlement counters
        (pending + leased + completed + abandoned = total submitted)
        between its OWN ticks and takes the larger of the two. The
        per-tick delta is noisy; :meth:`_smooth_demand` owns smoothing."""
        submitted = (int(agg.get("pending_jobs") or 0)
                     + int(agg.get("leased_jobs") or 0)
                     + int(agg.get("completed_jobs") or 0)
                     + int(agg.get("abandoned_jobs") or 0))
        anchor = self._arrival_anchor
        self._arrival_anchor = (now, submitted)
        hive_ewma = float(agg.get("observed_arrival_jobs_s") or 0.0)
        if anchor is None:
            return hive_ewma
        t0, submitted0 = anchor
        if now <= t0 or submitted < submitted0:
            return hive_ewma
        return max(hive_ewma, (submitted - submitted0) / (now - t0))

    def _refine_capacity(self, agg: dict[str, Any], actual: int,
                         now: float) -> float:
        """Online refinement of the per-worker capacity prior: settle
        throughput is a true capacity sample only while the fleet is
        SATURATED (hive-side backlog waiting), otherwise it just
        measures demand — so only saturated intervals blend in."""
        done = int(agg.get("completed_jobs") or 0)
        pending = int(agg.get("pending_jobs") or 0)
        anchor = self._throughput_anchor
        self._throughput_anchor = (now, done, pending, max(1, actual))
        if anchor is None:
            return self._capacity
        t0, done0, pending0, actual0 = anchor
        dt = now - t0
        if dt <= 0 or done <= done0 or pending0 <= 0:
            return self._capacity
        sample = (done - done0) / dt / actual0
        alpha = self.config.capacity_alpha
        self._capacity += alpha * (sample - self._capacity)
        return self._capacity

    # ---- placement -----------------------------------------------------

    def _plan_placement(self, model_rates: dict[str, float],
                        names: list[str]) -> dict[str, tuple[str, ...]]:
        """Per-worker model assignment from per-model demand: every
        observed model keeps at least one home; hot models replicate
        onto more workers in proportion to their demand share (capped
        at ``replicate_max``). Deterministic: models by (-rate, name),
        homes least-loaded-first — the same inputs always produce the
        same plan, so recovery replays placement exactly."""
        if not names:
            return {}
        names = sorted(names)
        total = sum(r for r in model_rates.values() if r > 0)
        load: dict[str, list[str]] = {name: [] for name in names}
        for model, rate in sorted(model_rates.items(),
                                  key=lambda kv: (-kv[1], kv[0])):
            share = (rate / total) if total > 0 else 0.0
            replicas = max(1, min(len(names), self.config.replicate_max,
                                  math.ceil(share * len(names))))
            homes = sorted(names, key=lambda n: (len(load[n]), n))
            for name in homes[:replicas]:
                load[name].append(model)
        return {name: tuple(models)
                for name, models in load.items() if models}

    def placement_for(self, worker_name: str) -> tuple[str, ...]:
        """The current plan's model list for one worker — what the
        hive piggybacks on that worker's heartbeat acks."""
        return self._placement.get(str(worker_name), ())

    # ---- the planning tick --------------------------------------------

    def tick(self, now: float | None = None) -> dict[str, Any]:
        """One observe->decide step. Returns the decision dict (also
        kept as :attr:`last_decision` and served at ``/api/plan``).

        ``direction`` is ``up``/``down`` only when the caller should
        actuate NOW: ``spawn`` names how many workers to add, ``drain``
        names the victims to retire gracefully. Actuating decisions —
        and placement changes — are journaled; steady holds are not
        (they carry no intent a recovery could double-apply, and a
        busy hive ticks far more often than it decides)."""
        cfg = self.config
        now = self._clock() if now is None else float(now)
        snapshot = self.hive.fleet_snapshot()
        agg = snapshot.get("aggregate") or {}
        workers = snapshot.get("workers") or {}
        # settle the draining ledger: a victim that left the snapshot
        # (or stopped heartbeating) has drained; one stuck past the
        # grace window re-enters the live view and is re-decided
        for name, decided_at in list(self._draining.items()):
            entry = workers.get(name)
            gone = entry is None or not entry.get("live")
            if gone or now - decided_at > 60.0:
                del self._draining[name]
        live = {name: w for name, w in workers.items()
                if w.get("live") and not w.get("partitioned")
                and name not in self._draining}
        actual = len(live)
        observed = self._observe_arrivals(agg, now)
        backlog = int(agg.get("pending_jobs") or 0)
        capacity = self._refine_capacity(agg, actual, now)
        smoothed = self._smooth_demand(observed, now)
        backlog_rate = backlog / max(1e-6, cfg.backlog_drain_s)
        demand = smoothed + backlog_rate
        per_worker = max(1e-6, capacity * cfg.target_utilization)
        raw = demand / per_worker
        raw_desired = math.ceil(raw - 1e-9)
        desired = max(cfg.min_workers,
                      min(cfg.max_workers, raw_desired))
        # worker-hours accrue continuously (actual x wall time) — the
        # cost the ISSUE-19 gate compares against static rosters
        if self._last_tick is not None and now > self._last_tick:
            self._m_hours.inc(actual * (now - self._last_tick) / 3600.0)
        self._last_tick = now

        direction, reason = "hold", "steady"
        if desired > actual:
            direction = "up"
            reason = ("backlog" if backlog_rate > smoothed else "demand")
            if actual > 0 and raw <= actual * (1.0 + cfg.hysteresis):
                direction, reason = "hold", "hysteresis"
            elif now - self._last_up < cfg.cooldown_up_s:
                direction, reason = "hold", "cooldown"
        elif desired < actual:
            direction, reason = "down", "demand"
            if raw >= actual * (1.0 - cfg.hysteresis):
                direction, reason = "hold", "hysteresis"
            elif (now - self._last_down < cfg.cooldown_down_s
                  or now - self._last_up < cfg.cooldown_down_s):
                # a fresh scale-up also pins scale-down — for the FULL
                # down cooldown, not just the up one: the spike that
                # forced the up is exactly when a momentarily-clear
                # backlog must not be read as "demand is gone"
                direction, reason = "hold", "cooldown"
        elif raw_desired > cfg.max_workers and actual >= cfg.max_workers:
            # demand asks for more than the ceiling allows: the hold is
            # a BOUNDS hold (an operator alert), not a steady one
            direction, reason = "hold", "bounds"
        elif raw_desired < cfg.min_workers and actual <= cfg.min_workers:
            direction, reason = "hold", "bounds"

        spawn = desired - actual if direction == "up" else 0
        drain: list[str] = []
        if direction == "down":
            # fewest leases drain first (cheapest preemption: least
            # checkpoint custody to move), deterministic tie-break
            victims = sorted(live,
                             key=lambda n: (live[n].get("leased_jobs", 0),
                                            n))
            drain = victims[:actual - desired]
            for name in drain:
                self._draining[name] = now
        survivors = [name for name in live if name not in set(drain)]
        model_rates = {
            str(m): float(r)
            for m, r in (agg.get("model_arrival_jobs_s") or {}).items()}
        placement = self._plan_placement(model_rates, survivors)
        moves = sum(
            1 for name, models in placement.items()
            for model in models
            if model not in self._placement.get(name, ()))
        placement_changed = placement != self._placement
        self._placement = placement

        decision: dict[str, Any] = {
            "at_s": round(now, 6),
            "direction": direction,
            "reason": reason,
            "target": desired,
            "actual": actual,
            "spawn": spawn,
            "drain": drain,
            "demand_jobs_s": round(demand, 4),
            "observed_jobs_s": round(observed, 4),
            "backlog_jobs": backlog,
            "capacity_jobs_s_per_worker": round(capacity, 4),
            "placement": {name: list(models)
                          for name, models in placement.items()},
        }
        if direction == "up":
            self._last_up = now
        elif direction == "down":
            self._last_down = now
        self.ticks += 1
        self.last_decision = decision
        self._m_target.set(desired)
        self._m_actual.set(actual)
        self._m_decisions.inc(direction=direction, reason=reason)
        if moves:
            self._m_moves.inc(moves)
        if direction != "hold" or placement_changed:
            self._journal_hive.record_plan(decision)
        if direction != "hold":
            log.info("plan: %s %s->%s (%s; demand %.2f jobs/s, capacity "
                     "%.2f/worker)%s", direction, actual, desired, reason,
                     demand, capacity,
                     f" drain={drain}" if drain else "")
        return decision

    # ---- the supervisor contract (GET /api/plan) -----------------------

    def plan_snapshot(self) -> dict[str, Any]:
        """The ``GET /api/plan`` body a real deployment's supervisor
        consumes: the latest decision plus the knobs that produced it
        (so an operator reading the endpoint can tell WHY the target
        is what it is)."""
        return {
            "config": dataclasses.asdict(self.config),
            "ticks": self.ticks,
            "decision": self.last_decision,
        }
