"""swarmload: the mini-hive load harness (ISSUE 9 / ROADMAP item 5).

PR 6 built the fleet substrate — :class:`~chiaswarm_tpu.node.minihive.
MiniHive` is a real lease-tracking queue already running multiple
Workers in one process under kill/partition faults — but nothing drove
it at fleet scale. This module is the LOAD side:

- **Synthetic users**: :class:`UserPopulation` builds thousands of
  users, each with a workload profile (txt2img burst, img2img trickle,
  inpaint/ControlNet tail), an activity weight, and a model affinity —
  the per-user structure real hive traffic has and a flat Poisson
  stream does not.
- **Arrival curves**: :class:`DiurnalCurve` compresses a day into the
  run — a seeded sinusoid with seeded spike windows layered on top.
  :func:`generate_schedule` expands (population x curve x duration)
  into a deterministic arrival schedule: same seed, same jobs, same
  timestamps, forever.
- **The drive**: :func:`run_load` submits the schedule into a
  :class:`LoadHive` (a MiniHive stamping submit/grant/settle times per
  job) against real :class:`~chiaswarm_tpu.node.worker.Worker`
  processes — their actual poll loops, overload controllers, queues,
  and upload paths. Workers execute through the chaos-harness executor
  seam by default (:class:`SyntheticExecutor`, deterministic
  per-workload service times, no compiles), or through real pipelines
  when the caller passes its own factory; an optional scripted worker
  kill lands mid-run through the PR-6 partition + preemption path.
- **Scoring**: :func:`score_run` reconciles exactly-once settlement
  (every issued job completed, shed-redispatched, or abandoned-by-
  policy — zero lost), folds per-workload p50/p99 latency, admitted-
  within-deadline conformance, the workers' ``/metrics``-level
  snapshots (occupancy, padding waste, breaker trips, overload and
  residency families), and publishes a **capacity model**: jobs/s per
  chip per workload mix, with models-resident as the second axis —
  the numbers that turn "fast in a benchmark" into "provisionable".

The same arrival model doubles as the tuning harness (the ISSUE-9
satellite): :func:`sweep_lane_gains` replays seeded traces through
:class:`~chiaswarm_tpu.serving.stepper.LaneWidthController` in pure
host simulation to score grow/shrink/patience gains, and
:func:`sweep_prefetch_window` scores the residency
:class:`~chiaswarm_tpu.serving.residency.ArrivalEwma` prefetch-ranking
window the same way.

Like the chaos harness, this is product code: operators smoke a build's
overload behavior with ``python -m chiaswarm_tpu.node.loadgen``
(JSON report on stdout; ``CHIASWARM_LOAD_*`` knobs below), and
``tests/test_loadgen.py`` is the executable spec — including THE
ISSUE-9 acceptance gate: scripted 10x overload, mixed workloads, one
mid-run worker kill, zero job loss, p99 of admitted jobs within
deadline.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import math
import os
import random
import threading
import time
from typing import Any, Callable, Iterable, Sequence

from chiaswarm_tpu.node.federation import FederatedHive, ShardHive
from chiaswarm_tpu.node.minihive import MiniHive
from chiaswarm_tpu.node.output_processor import make_text_result
from chiaswarm_tpu.node.resilience import classify_result
from chiaswarm_tpu.obs import trace as obs_trace
from chiaswarm_tpu.obs.flight import ATTRIBUTION_PHASES

log = logging.getLogger("chiaswarm.loadgen")


def _suggest_hang_budget() -> dict:
    """The guard's measured hang-budget derivation over THIS process's
    step-seconds histogram (import deferred: loadgen is host-only)."""
    from chiaswarm_tpu.serving.guard import suggest_hang_budget

    return suggest_hang_budget()


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 1]) of an unsorted sequence;
    0.0 for an empty one. One definition, so a p99 always means the
    same thing."""
    if not values:
        return 0.0
    ordered = sorted(values)
    rank = min(len(ordered) - 1, max(0, math.ceil(q * len(ordered)) - 1))
    return float(ordered[rank])


# ---------------------------------------------------------------------------
# workload profiles + users
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkloadProfile:
    """One workload class in the mix.

    ``weight`` is the share of the user population on this profile;
    ``deadline_s`` rides each job as its ``deadline_s`` field (the
    overload controller's admission budget and the scorer's
    conformance bound); ``steps`` bounds the sampled step count;
    ``service_s`` is the synthetic executor's base wall time."""

    name: str
    weight: float
    deadline_s: float
    steps: tuple[int, int]
    service_s: float


#: the default mix the ISSUE names: txt2img burst, img2img trickle,
#: inpaint + ControlNet tail — plus the few-step class (ISSUE 12):
#: LCM/turbo-style 2–8 step jobs are interactive traffic, so they carry
#: the SHORTEST deadline in the mix and the smallest service time
#: (steps x per-step cost collapses ~7x vs the 30-step baseline).
#: Service times are the synthetic stand-in scale (hermetic runs);
#: real-pipeline factories ignore them.
DEFAULT_PROFILES: tuple[WorkloadProfile, ...] = (
    WorkloadProfile("txt2img", 0.50, 2.0, (10, 30), 0.10),
    WorkloadProfile("txt2img_fewstep", 0.15, 0.8, (2, 8), 0.04),
    WorkloadProfile("img2img", 0.22, 2.5, (10, 25), 0.13),
    WorkloadProfile("inpaint", 0.08, 3.0, (10, 25), 0.16),
    WorkloadProfile("controlnet", 0.05, 3.0, (15, 30), 0.20),
)


# ---------------------------------------------------------------------------
# per-model-family deadline tables (ISSUE 10 satellite, ROADMAP 5b)
# ---------------------------------------------------------------------------
#
# The PR-2 deadlines were static per-WORKFLOW guesses; a family that
# costs 3x the denoise FLOPs deserves 3x the budget. The harness closes
# the loop two ways: score_run() emits a measured suggested-deadline
# table (p99 x margin) per family from every run, and
# sweep_deadline_table() is the pure deterministic derivation whose
# output ships as DEFAULT_FAMILY_DEADLINES — pinned defaults == winner
# by tests/test_loadgen.py, exactly like the PR-9 controller-gain
# sweep. Operators apply a table via the ``family_deadline_s`` settings
# map (node/settings.py; the worker consults it between a job's
# explicit deadline_s and the workflow table).

#: headroom multiplier over the measured p99 — an admitted job that
#: misses by 50 ms still misses, and the estimator cannot see ack
#: jitter (the PR-9 margin lesson, applied to the budget side)
DEADLINE_MARGIN = 1.5

#: relative denoise cost per model family (sd15 = 1.0; sdxl's 3.2 is
#: a step-time ratio of an installation that is gone — on this chip
#: the width-1 lane steps read 121.85 / 19.956 ms = 6.1, ledger, PR 29
#: — and tiny is the test family's share) — scales the synthetic
#: service model, which times sleeps and never the chip, the
#: same way the family scales the real denoise loop. ``sdxl_turbo``
#: (ISSUE 12) is the few-step-distilled SDXL class: the per-step cost
#: stays SDXL's 3.2 but 4 steps replace 30, so 3.2 x 4/30 ≈ 0.43 —
#: the family-deadline table prices few-step jobs at their collapsed
#: cost instead of billing them the 30-step budget.
FAMILY_COST_FACTORS = {"tiny": 0.12, "sd15": 1.0, "sdxl": 3.2,
                       "sdxl_turbo": 0.43, "sd_turbo": 0.13}


def model_family(name: Any) -> str:
    """Family bucket of a model name for the deadline table. A light
    name heuristic on purpose: the scorer must run without jax or the
    model-config registry (the worker side uses the real catalog,
    node/worker.py::_model_family)."""
    lowered = str(name or "").lower()
    if "turbo" in lowered or "lcm" in lowered or "lightning" in lowered:
        # the distilled few-step classes, checked BEFORE the "xl" hint
        # ("sdxl-turbo" names both); non-XL distillations (sd-turbo,
        # sd15-lcm) price at the SD-class per-step cost, not SDXL's
        return "sdxl_turbo" if "xl" in lowered else "sd_turbo"
    if "xl" in lowered:
        return "sdxl"
    if "tiny" in lowered:
        return "tiny"
    return "sd15"


def sweep_deadline_table(seed: Any = "swarmload", *,
                         margin: float = DEADLINE_MARGIN,
                         samples: int = 4000,
                         profiles: Sequence[WorkloadProfile] =
                         DEFAULT_PROFILES,
                         factors: dict[str, float] | None = None,
                         ) -> dict[str, float]:
    """Derive a per-family deadline table from the harness's service
    model: seeded mix-weighted service draws (the SyntheticExecutor's
    jitter model) scaled by each family's cost factor, doubled for one
    queued-peer drain (the admission estimator's occupancy~1 term),
    p99 x margin. Pure host arithmetic, deterministic per seed — the
    shipped DEFAULT_FAMILY_DEADLINES is this function's output at the
    default seed, pinned by test."""
    factors = dict(FAMILY_COST_FACTORS if factors is None else factors)
    weights = [max(0.0, p.weight) for p in profiles]
    table: dict[str, float] = {}
    for family, factor in sorted(factors.items()):
        rng = random.Random(f"deadline:{seed}:{family}")
        draws = []
        for _ in range(max(1, int(samples))):
            profile = rng.choices(list(profiles), weights=weights)[0]
            jitter = 1.0 + 0.3 * (2.0 * rng.random() - 1.0)
            draws.append(profile.service_s * factor * jitter * 2.0)
        table[family] = round(percentile(draws, 0.99) * margin, 3)
    return table


#: the shipped per-family deadline defaults — sweep_deadline_table()'s
#: output at the default seed (pinned defaults == winner,
#: tests/test_loadgen.py::test_family_deadline_defaults_pinned).
#: ``sdxl_turbo`` (ISSUE 12) prices the few-step-distilled SDXL class
#: at its collapsed step count — ~7x tighter than full SDXL.
DEFAULT_FAMILY_DEADLINES = {"sd15": 0.713, "sd_turbo": 0.094,
                            "sdxl": 2.257, "sdxl_turbo": 0.31,
                            "tiny": 0.086}


@dataclasses.dataclass(frozen=True)
class SyntheticUser:
    user_id: int
    profile: WorkloadProfile
    activity: float        # relative arrival weight within the population
    model: str             # the checkpoint this user's jobs name


class UserPopulation:
    """``n_users`` seeded synthetic users over a workload mix.

    Activity weights are heavy-tailed (a few power users, a long tail
    of occasional ones — ``0.2 + Pareto``), and each user sticks to one
    model from ``models`` so the stream has the per-model locality the
    residency ledger's prefetch ranking feeds on."""

    def __init__(self, n_users: int = 2000,
                 profiles: Sequence[WorkloadProfile] = DEFAULT_PROFILES,
                 models: Sequence[str] = ("swarm/sd15",),
                 seed: Any = "swarmload") -> None:
        if not profiles:
            raise ValueError("need at least one workload profile")
        self.profiles = tuple(profiles)
        self.seed = seed
        rng = random.Random(f"users:{seed}")
        weights = [max(0.0, p.weight) for p in self.profiles]
        names = list(models) or ["swarm/sd15"]
        self.users: list[SyntheticUser] = []
        for uid in range(max(1, int(n_users))):
            profile = rng.choices(self.profiles, weights=weights)[0]
            activity = 0.2 + rng.paretovariate(2.0)
            model = rng.choices(names,
                                weights=range(len(names), 0, -1))[0]
            self.users.append(SyntheticUser(uid, profile, activity, model))
        self._cum_activity = []
        total = 0.0
        for user in self.users:
            total += user.activity
            self._cum_activity.append(total)
        self.total_activity = total

    def pick(self, rng: random.Random) -> SyntheticUser:
        """Activity-weighted user draw (bisect over the cumulative
        weights — O(log n) per arrival at thousands of users)."""
        import bisect

        x = rng.uniform(0.0, self.total_activity)
        return self.users[min(len(self.users) - 1,
                              bisect.bisect_left(self._cum_activity, x))]

    def mix(self) -> dict[str, float]:
        counts: dict[str, int] = {}
        for user in self.users:
            counts[user.profile.name] = counts.get(user.profile.name, 0) + 1
        return {name: round(n / len(self.users), 4)
                for name, n in sorted(counts.items())}


# ---------------------------------------------------------------------------
# arrival curves
# ---------------------------------------------------------------------------


class DiurnalCurve:
    """Seeded diurnal + spike rate multiplier over one compressed "day".

    ``multiplier(frac)`` (frac = t / duration in [0, 1]) is a sinusoid
    — trough at the start, peak mid-run — of ``amplitude`` around 1.0,
    with ``spikes`` seeded spike windows (each ``spike_frac`` of the
    run at ``spike_mult`` x) layered on top: the flash-crowd shape that
    makes overload control earn its keep. Deterministic per seed."""

    def __init__(self, *, amplitude: float = 0.6, spikes: int = 2,
                 spike_mult: float = 4.0, spike_frac: float = 0.06,
                 seed: Any = "swarmload") -> None:
        self.amplitude = max(0.0, min(1.0, float(amplitude)))
        self.spike_mult = max(1.0, float(spike_mult))
        rng = random.Random(f"curve:{seed}")
        width = max(1e-3, float(spike_frac))
        self.spike_windows = sorted(
            (start, min(1.0, start + width))
            for start in (rng.uniform(0.15, 0.9 - width)
                          for _ in range(max(0, int(spikes)))))

    def multiplier(self, frac: float) -> float:
        frac = max(0.0, min(1.0, float(frac)))
        base = 1.0 + self.amplitude * math.sin(
            2.0 * math.pi * (frac - 0.25))
        for start, end in self.spike_windows:
            if start <= frac < end:
                return base * self.spike_mult
        return base


@dataclasses.dataclass(frozen=True)
class ScheduledJob:
    at_s: float
    user_id: int
    workload: str
    job: dict[str, Any]


def generate_schedule(population: UserPopulation,
                      curve: DiurnalCurve, *,
                      duration_s: float,
                      rate_jobs_s: float,
                      seed: Any = "swarmload",
                      id_prefix: str = "load",
                      content_type: str = "application/json",
                      ) -> list[ScheduledJob]:
    """Expand (population x curve) into a deterministic arrival list.

    Arrivals are a thinned Poisson process: exponential inter-arrival
    gaps at the peak rate, each kept with probability
    ``multiplier / peak`` — so the instantaneous accepted rate tracks
    ``rate_jobs_s x curve.multiplier`` exactly, with no time-bucket
    artifacts. Each accepted arrival draws an activity-weighted user,
    whose profile supplies workload, steps, deadline, and model."""
    rng = random.Random(f"schedule:{seed}")
    duration_s = max(1e-3, float(duration_s))
    rate = max(1e-6, float(rate_jobs_s))
    peak = rate * max(curve.multiplier(f / 200.0) for f in range(201))
    out: list[ScheduledJob] = []
    t = 0.0
    n = 0
    while True:
        t += rng.expovariate(peak)
        if t >= duration_s:
            break
        if rng.uniform(0.0, peak) > rate * curve.multiplier(t / duration_s):
            continue  # thinned: off-peak slack
        user = population.pick(rng)
        profile = user.profile
        steps = rng.randint(*profile.steps)
        job_id = f"{id_prefix}-{n}"
        job: dict[str, Any] = {
            "id": job_id,
            "model_name": user.model,
            "workflow": profile.name,
            "prompt": f"user {user.user_id} {profile.name} {n}",
            "num_inference_steps": steps,
            "guidance_scale": 7.5,
            "height": 64, "width": 64,
            "seed": rng.randrange(1 << 31),
            "deadline_s": profile.deadline_s,
            # "application/json" for synthetic executors; the REAL-lane
            # soak passes "image/png" so real pipelines encode actual
            # frames (ISSUE 10 satellite / ROADMAP 5a)
            "content_type": content_type,
        }
        if profile.name == "txt2img_fewstep":
            # the few-step class IS the lcm-kind CFG-free path
            # (ISSUE 12): real-pipeline runs must exercise the fewstep
            # lane eligibility + per-row CFG-free combine, not a short
            # dpm job wearing the class name
            job["guidance_scale"] = 1.0
            job["parameters"] = {"scheduler_type": "LCMScheduler"}
        out.append(ScheduledJob(at_s=t, user_id=user.user_id,
                                workload=profile.name, job=job))
        n += 1
    return out


# ---------------------------------------------------------------------------
# the drive: LoadHive + synthetic workers
# ---------------------------------------------------------------------------


class LoadHive(MiniHive):
    """MiniHive with per-job timing stamps for the scorer.

    ``submitted_at`` comes from MiniHive (it also rides the wire as
    each delivery's ``queued_s`` age stamp); ``granted_at`` re-stamps
    on every delivery (the "admitted latency" view runs from the LAST
    grant — the delivery that produced the settling envelope);
    ``settled_at`` stamps the exactly-once settle."""

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        # submitted_at comes from MiniHive itself (it also feeds the
        # wire "queued_s" stamp every delivery carries)
        self.granted_at: dict[str, float] = {}
        self.settled_at: dict[str, float] = {}

    def submit_job(self, job: dict[str, Any]) -> None:
        self.submit(job)

    def _take_jobs(self, worker_name: str):
        out = super()._take_jobs(worker_name)
        now = self._clock()
        for payload in out:
            self.granted_at[str(payload.get("id"))] = now
        return out

    def _record_result(self, result, worker_name):
        ack = super()._record_result(result, worker_name)
        if ack.get("status") == "ok":
            self.settled_at[str(result.get("id"))] = self._clock()
        return ack


class _ShardLoad(ShardHive, LoadHive):
    """One federated load shard: ShardHive's steal/forward seams
    stacked over LoadHive's timing stamps. steal_to's cooperative
    ``super()._take_jobs`` resolves through LoadHive here, so STOLEN
    grants stamp ``granted_at`` exactly like owned ones, and a
    forwarded wrong-shard upload settles (and stamps ``settled_at``)
    on the owner — the scorer never sees federation seams."""


class _StitchedFlights:
    """score_run's flight view over a federation: each lookup routes
    to the job's OWNING shard (the only book that flight lives in)."""

    def __init__(self, federation: "FederatedLoadHive") -> None:
        self._federation = federation

    def get(self, job_id: Any) -> dict | None:
        shard = self._federation.owner_shard(job_id)
        return None if shard is None else shard.flights.get(job_id)

    def verify(self, job_ids: Iterable[Any]) -> list:
        return self._federation.verify_flights(job_ids)


class FederatedLoadHive(FederatedHive):
    """The federation wired for the load harness (swarmfed, ISSUE 17):
    _ShardLoad shards plus the merged timing views :func:`score_run`
    folds. Everything else — routing, stealing, per-shard journals —
    is stock FederatedHive."""

    def __init__(self, n_shards: int = 3, **kwargs: Any) -> None:
        kwargs.setdefault("hive_cls", _ShardLoad)
        super().__init__(n_shards, **kwargs)

    def _merged(self, attr: str) -> dict[str, float]:
        out: dict[str, float] = {}
        for shard in self.shards:
            out.update(getattr(shard, attr, {}))
        return out

    @property
    def granted_at(self) -> dict[str, float]:
        return self._merged("granted_at")

    @property
    def settled_at(self) -> dict[str, float]:
        return self._merged("settled_at")

    @property
    def flights(self) -> _StitchedFlights:
        return _StitchedFlights(self)


class SyntheticExecutor:
    """Executor seam stand-in with deterministic per-workload service
    times (the load-harness analog of ChaoticExecutor: exercises the
    REAL worker — poll loop, queues, shed gate, backpressure, uploads —
    without compiling a pipeline). Service = the job workload's base
    time x a seeded jitter factor, reproducible per (job, attempt)."""

    def __init__(self, profiles: Sequence[WorkloadProfile] =
                 DEFAULT_PROFILES, *, jitter: float = 0.3,
                 seed: Any = "swarmload") -> None:
        self.service_s = {p.name: p.service_s for p in profiles}
        self.default_s = min(self.service_s.values(), default=0.1)
        self.jitter = max(0.0, min(0.9, float(jitter)))
        self.seed = seed
        self.attempts: dict[str, int] = {}
        self.executed: list[str] = []

    def _service(self, job: dict[str, Any]) -> float:
        job_id = str(job.get("id"))
        attempt = self.attempts.get(job_id, 0) + 1
        self.attempts[job_id] = attempt
        rng = random.Random(f"svc:{self.seed}:{job_id}:{attempt}")
        base = self.service_s.get(str(job.get("workflow") or "txt2img"),
                                  self.default_s)
        return base * (1.0 + self.jitter * (2.0 * rng.random() - 1.0))

    async def _run_one(self, job: dict[str, Any]) -> dict[str, Any]:
        # the synthetic service time stands in for the denoise loop, so
        # it records as a "step" span under the job's execute phase —
        # the flight record's budget attribution (ISSUE 13) then books
        # it as steps, not unattributed residue. Manual child (not
        # span()): custom executors run on the event loop where the
        # trace contextvar is never activated.
        trace = obs_trace.job_trace(job)
        step = trace.tail().child("step") if trace is not None else None
        await asyncio.sleep(self._service(job))
        if step is not None:
            step.end()
        self.executed.append(str(job.get("id")))
        return {
            "id": job.get("id"),
            "artifacts": {"primary": make_text_result(
                f"load ok: {job.get('id')}")},
            "nsfw": False,
            "worker_version": "loadgen",
            "pipeline_config": {
                "workload": str(job.get("workflow") or "txt2img"),
                "attempt": self.attempts.get(str(job.get("id")), 1)},
        }

    async def do_work(self, job, slot, registry) -> dict:
        return await self._run_one(job)

    async def do_work_batch(self, jobs, slot, registry) -> list[dict]:
        return [await self._run_one(job) for job in jobs]


def default_worker_factory(profiles: Sequence[WorkloadProfile] =
                           DEFAULT_PROFILES, seed: Any = "swarmload",
                           **settings_over: Any) -> Callable[[str, str],
                                                             Any]:
    """A factory building overload-controlled synthetic workers — the
    harness default. Callers with real pipelines pass their own
    ``worker_factory(uri, name) -> Worker`` instead."""
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    class _StubSlot:
        # deeper than the chip-pool default: the worker's work_queue
        # bound is the slot depth, and backpressure needs a few queued
        # jobs' drain estimate to meaningfully exceed its budget
        depth = 6
        data_width = 1

        def __init__(self, name: str) -> None:
            self.name = name

        def descriptor(self) -> str:
            return self.name

    def factory(uri: str, name: str):
        base = dict(
            hive_uri=uri, hive_token="t", worker_name=name,
            poll_busy_s=0.02, poll_idle_s=0.05,
            poll_backoff_base_s=0.02, poll_backoff_cap_s=0.2,
            upload_retries=5, upload_retry_delay_s=0.02,
            transient_retries=1, retry_backoff_s=0.01,
            retry_backoff_cap_s=0.05,
            drain_timeout_s=10.0, result_drain_timeout_s=10.0,
            install_signal_handlers=False,
            heartbeat_s=0.1,
            overload_control=True,
            # the execution cap stays generous (it is the PR-2 timeout
            # envelope, not the admission budget); backpressure keys on
            # the harness's seconds-scale job deadlines instead
            job_deadline_s=30.0,
            backpressure_s=0.5,
            # shed with headroom: the estimator cannot see the next
            # poll's latency or ack jitter, and an admitted job that
            # misses by 50 ms still misses — 0.8 holds zero deadline
            # violations across the seeded 10x + worker-kill runs
            overload_margin=0.8,
        )
        base.update(settings_over)
        return Worker(settings=Settings(**base),
                      pool=[_StubSlot(name)],
                      registry=ModelRegistry(catalog=[],
                                             allow_random=True),
                      executor=SyntheticExecutor(profiles, seed=seed))

    return factory


@dataclasses.dataclass(frozen=True)
class KillPlan:
    """Scripted mid-run worker kill: once ``after_frac`` of the
    schedule has been submitted, the first worker holding a lease is
    partitioned, cancelled, and lease-revoked (the PR-6 preemption
    path) — its jobs redeliver to the survivors."""

    after_frac: float = 0.5


@dataclasses.dataclass(frozen=True)
class RosterPlan:
    """Scripted fleet churn (ROADMAP item 5 residue, ISSUE 14
    satellite): workers JOIN and LEAVE mid-run, exercising the capacity
    model and ``GET /api/fleet`` under elastic rosters rather than only
    kills. Each entry is a fraction of the schedule: at ``join_at``
    fractions a NEW worker (from the same factory) starts polling; at
    ``leave_at`` fractions one running worker drains GRACEFULLY
    (request_stop — in-flight jobs complete and upload; nothing
    redelivers) and leaves. Distinct from :class:`KillPlan` on purpose:
    an autoscaler's scale-down is a drain, not a preemption."""

    join_at: tuple[float, ...] = ()
    leave_at: tuple[float, ...] = ()


@dataclasses.dataclass(frozen=True)
class AutoscalePlan:
    """swarmplan (ISSUE 19): run the fleet ELASTICALLY under the
    hive-side :class:`~chiaswarm_tpu.node.planner.FleetPlanner` instead
    of a scripted roster. The harness starts ``min_workers``, ticks the
    planner every ``tick_every_s`` wall seconds, and actuates its
    decisions through the SAME seams a real deployment uses: scale-up
    spawns workers from the run's factory (the supervisor leg —
    real deployments poll ``GET /api/plan``); scale-down drains
    gracefully (``request_stop`` + lease preemption via
    ``expire_worker`` — never the kill path; mid-lane rows checkpoint
    and redeliver-with-resume to survivors). The remaining fields are
    :class:`~chiaswarm_tpu.node.planner.PlannerConfig` passthrough."""

    min_workers: int = 1
    max_workers: int = 6
    tick_every_s: float = 0.25
    target_utilization: float = 0.6
    smoothing_window_s: float = 2.0
    hysteresis: float = 0.15
    cooldown_up_s: float = 0.5
    cooldown_down_s: float = 2.5
    backlog_drain_s: float = 2.0
    capacity_jobs_s_per_worker: float = 6.0
    capacity_alpha: float = 0.3
    replicate_max: int = 3

    def planner_config(self):
        from chiaswarm_tpu.node.planner import PlannerConfig

        return PlannerConfig(
            min_workers=int(self.min_workers),
            max_workers=int(self.max_workers),
            target_utilization=float(self.target_utilization),
            smoothing_window_s=float(self.smoothing_window_s),
            hysteresis=float(self.hysteresis),
            cooldown_up_s=float(self.cooldown_up_s),
            cooldown_down_s=float(self.cooldown_down_s),
            backlog_drain_s=float(self.backlog_drain_s),
            capacity_jobs_s_per_worker=float(
                self.capacity_jobs_s_per_worker),
            capacity_alpha=float(self.capacity_alpha),
            replicate_max=int(self.replicate_max),
        )


class ContentionProbe:
    """Host-contention sampler (ISSUE 12, promoted to a reusable class
    for the ISSUE 17 guard-gate deflake): a daemon THREAD measures how
    late ``time.sleep`` fires while a harness runs (~1.0 on an idle
    host). Timing gates bound their clauses against the measured
    factor instead of absolute wall clock, so a contended CI host
    loosens a bound by exactly the measured sleep stretch — never by
    an arbitrary fudge. Deliberately NOT an asyncio task on the
    harness loop: loop lag caused by the code under test must count
    against the gate, not loosen it — the thread sees only host-level
    scheduling delay."""

    def __init__(self, tick_s: float = 0.02) -> None:
        self.tick_s = max(1e-4, float(tick_s))
        self.overshoots: list[float] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._sample, name="contention-probe", daemon=True)

    def _sample(self) -> None:
        while not self._stop.is_set():
            t0 = time.perf_counter()
            time.sleep(self.tick_s)
            self.overshoots.append(
                (time.perf_counter() - t0) / self.tick_s)

    def start(self) -> "ContentionProbe":
        self._thread.start()
        return self

    def stop(self) -> float:
        """Stop sampling; returns the factor (callers may also keep
        reading :attr:`factor` afterwards)."""
        self._stop.set()
        self._thread.join(timeout=1.0)
        return self.factor

    @property
    def factor(self) -> float:
        """p90 sleep overshoot, floored at 1.0 (a bound scaled by this
        can loosen under contention but never tighten below nominal)."""
        if not self.overshoots:
            return 1.0
        return max(1.0, percentile(self.overshoots, 0.9))

    def report(self) -> dict[str, Any]:
        return {
            "sleep_overshoot_p90": (round(
                percentile(self.overshoots, 0.9), 4)
                if self.overshoots else 1.0),
            "samples": len(self.overshoots),
            "factor": round(self.factor, 4),
        }


async def run_load(schedule: Sequence[ScheduledJob], *,
                   n_workers: int = 3,
                   n_shards: int = 1,
                   worker_factory: Callable[[str, str], Any] | None = None,
                   hive: LoadHive | None = None,
                   lease_s: float = 5.0,
                   max_jobs_per_poll: int = 2,
                   max_attempts: int = 4,
                   kill: KillPlan | None = None,
                   roster: "RosterPlan | None" = None,
                   autoscale: "AutoscalePlan | None" = None,
                   on_submit: Callable[[int, Any], Any] | None = None,
                   time_scale: float = 1.0,
                   settle_timeout_s: float = 300.0,
                   seed: Any = "swarmload") -> dict[str, Any]:
    """Drive ``schedule`` through a LoadHive + ``n_workers`` Workers;
    returns :func:`score_run`'s report (plus the kill record). The
    harness owns worker lifecycle end to end — every worker drains (or
    is killed by plan) before scoring.

    ``n_shards > 1`` (swarmfed, ISSUE 17) drives the SAME schedule
    through a :class:`FederatedLoadHive` instead: jobs route by the
    stable hash, workers multiplex one session per shard (the
    comma-joined shard uris parse back through Settings.hive_uris),
    and idle shards steal from deep ones — the report's reconciliation
    and latency folds are fleet-wide.

    ``autoscale`` (swarmplan, ISSUE 19) replaces the static roster with
    the planner loop: ``n_workers`` is ignored, the fleet starts at
    ``autoscale.min_workers`` and grows/shrinks per planning tick.
    Every run (elastic or static) reports ``worker_time`` — summed
    worker lifetime seconds — so the autoscaler gate can compare
    worker-hours against static rosters on equal terms."""
    if hive is None:
        if int(n_shards) > 1:
            hive = FederatedLoadHive(
                int(n_shards), lease_s=lease_s, delay_s=0.0,
                max_attempts=max_attempts,
                max_jobs_per_poll=max_jobs_per_poll)
        else:
            hive = LoadHive(lease_s=lease_s, delay_s=0.0,
                            max_attempts=max_attempts,
                            max_jobs_per_poll=max_jobs_per_poll)
    factory = worker_factory or default_worker_factory(seed=seed)
    uri = await hive.start()
    if hasattr(hive, "worker_uri"):  # federation: workers dial shards
        uri = hive.worker_uri()
    initial_n = (max(1, int(autoscale.min_workers))
                 if autoscale is not None else max(1, int(n_workers)))
    workers = [factory(uri, f"load-{seed}-w{i}")
               for i in range(initial_n)]
    # per-worker lifetime ledger (swarmplan): every task stamps its
    # start at creation and its stop via done-callback, so the report's
    # worker-hours mean the same thing for static and elastic fleets
    worker_started: dict[str, float] = {}
    worker_stopped: dict[str, float] = {}
    tasks: dict[str, asyncio.Task] = {}

    def _track(name: str, task: "asyncio.Task") -> "asyncio.Task":
        worker_started[name] = time.perf_counter()
        task.add_done_callback(
            lambda _t, n=name: worker_stopped.setdefault(
                n, time.perf_counter()))
        tasks[name] = task
        return task

    for w in workers:
        _track(w.settings.worker_name, asyncio.create_task(w.run()))
    ordered = sorted(schedule, key=lambda s: s.at_s)
    issued = [str(s.job["id"]) for s in ordered]
    kill_at = (math.ceil(len(ordered) * max(0.0, min(1.0,
                                                     kill.after_frac)))
               if kill is not None else None)
    killed: dict[str, Any] = {}
    # fleet churn (ISSUE 14 satellite): scripted joins/leaves become
    # per-index thresholds like the kill plan; events are recorded for
    # the report so a soak can assert the churn actually happened
    def _fracs_to_indices(fracs) -> list[int]:
        return sorted(math.ceil(len(ordered) * max(0.0, min(1.0, f)))
                      for f in (fracs or ()))

    joins_due = _fracs_to_indices(roster.join_at if roster else ())
    leaves_due = _fracs_to_indices(roster.leave_at if roster else ())
    roster_events: list[dict[str, Any]] = []
    joined_n = 0
    departed: set[str] = set()
    t_start = time.perf_counter()

    # contention probe (ISSUE 12 deflake): the harness runs on real
    # wall clocks, so a contended CI host stretches every latency in
    # the report — including the deadline-conformance numbers the
    # acceptance gate asserts on; the gate bounds latency ratios
    # against the measured factor instead of absolute wall clock.
    probe = ContentionProbe().start()

    async def maybe_kill() -> None:
        # first leaseholder found after the threshold dies NOW:
        # partition (nothing it uploads lands) + cancel (the process
        # "dies") + expire (the preemption notice redelivers its jobs)
        for worker in workers:
            name = worker.settings.worker_name
            leased = hive.leased_ids(name)
            if leased:
                killed.update(worker=name, jobs=list(leased))
                hive.partition(name)
                tasks[name].cancel()
                await asyncio.gather(tasks[name], return_exceptions=True)
                hive.expire_worker(name)
                log.warning("load kill: %s (held %d lease(s))", name,
                            len(leased))
                return

    async def apply_roster(done: int) -> None:
        nonlocal joined_n
        while joins_due and done >= joins_due[0]:
            joins_due.pop(0)
            joined_n += 1
            name = f"load-{seed}-join{joined_n}"
            worker = factory(uri, name)
            workers.append(worker)
            _track(name, asyncio.create_task(worker.run()))
            roster_events.append({"at_job": done, "action": "join",
                                  "worker": name})
            log.info("roster: %s joined after %d submissions", name,
                     done)
        while leaves_due and done >= leaves_due[0]:
            # first worker still serving (never killed, never left)
            candidate = next(
                (w for w in workers
                 if w.settings.worker_name not in departed
                 and w.settings.worker_name != killed.get("worker")),
                None)
            if candidate is None:
                leaves_due.clear()
                break
            leaves_due.pop(0)
            name = candidate.settings.worker_name
            departed.add(name)
            candidate.request_stop()  # graceful: drains, uploads, exits
            # shield: a slow drain must NOT be cancelled into a covert
            # kill (that would redeliver its jobs and contradict the
            # clean "leave" this records) — on timeout the worker keeps
            # draining and the final cleanup reaps it; the event says so
            drained = True
            try:
                await asyncio.wait_for(asyncio.shield(tasks[name]),
                                       timeout=60)
            except Exception:
                drained = tasks[name].done()
            roster_events.append({"at_job": done, "action": "leave",
                                  "worker": name, "drained": drained})
            log.info("roster: %s %s after %d submissions", name,
                     "drained and left" if drained
                     else "leaving (drain still in progress)", done)

    # swarmplan (ISSUE 19): the observe -> decide -> actuate loop. The
    # planner only DECIDES; this harness is the actuator — the same
    # division a real deployment has, where a supervisor polls
    # GET /api/plan and runs the container orchestration.
    planner = None
    auto_task: asyncio.Task | None = None
    auto_events: list[dict[str, Any]] = []
    auto_sizes: list[list[float]] = []
    auto_drains: dict[str, asyncio.Task] = {}
    auto_spawned = 0
    if autoscale is not None:
        from chiaswarm_tpu.node.planner import FleetPlanner

        planner = FleetPlanner(hive, autoscale.planner_config())

        def _spawn_auto(count: int) -> None:
            nonlocal auto_spawned
            for _ in range(count):
                auto_spawned += 1
                name = f"load-{seed}-auto{auto_spawned}"
                worker = factory(uri, name)
                workers.append(worker)
                _track(name, asyncio.create_task(worker.run()))
                log.info("autoscale: spawned %s", name)

        async def _drain_auto(name: str) -> None:
            # graceful scale-down, NEVER the kill path: stop polling
            # (in-flight work checkpoints and uploads), then preempt
            # the leases so mid-lane rows redeliver-with-resume to
            # survivors; the hive's exactly-once settle dedupes the
            # race between the victim's final upload and the resume
            worker = next((w for w in workers
                           if w.settings.worker_name == name), None)
            if worker is not None:
                worker.request_stop()
            hive.expire_worker(name)
            task = tasks.get(name)
            if task is not None:
                try:
                    await asyncio.wait_for(asyncio.shield(task),
                                           timeout=60)
                except Exception:
                    pass
            log.info("autoscale: drained %s", name)

        async def _autoscale_loop() -> None:
            while True:
                await asyncio.sleep(max(1e-3,
                                        float(autoscale.tick_every_s)))
                decision = planner.tick()
                rel_s = round(time.perf_counter() - t_start, 3)
                auto_sizes.append([rel_s, int(decision["actual"])])
                if decision["direction"] == "up" and decision["spawn"]:
                    # spawn against the HARNESS's liveness ledger, not
                    # the snapshot's: freshly spawned workers take a
                    # heartbeat to register, and re-spawning for them
                    # would overshoot the target
                    alive = sum(
                        1 for w in workers
                        if w.settings.worker_name not in departed
                        and w.settings.worker_name != killed.get(
                            "worker"))
                    _spawn_auto(min(int(decision["spawn"]),
                                    max(0, int(decision["target"])
                                        - alive)))
                elif decision["direction"] == "down":
                    for name in decision["drain"]:
                        if name in departed or name in auto_drains:
                            continue
                        departed.add(name)
                        auto_drains[name] = asyncio.create_task(
                            _drain_auto(name))
                if decision["direction"] != "hold":
                    auto_events.append({
                        "rel_s": rel_s,
                        **{k: decision[k] for k in (
                            "direction", "reason", "target", "actual",
                            "spawn", "drain")},
                    })

        auto_task = asyncio.create_task(_autoscale_loop())

    try:
        for i, item in enumerate(ordered):
            target = t_start + item.at_s * max(1e-3, float(time_scale))
            delay = target - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            hive.submit_job(dict(item.job))
            if kill_at is not None and not killed and i + 1 >= kill_at:
                await maybe_kill()
            await apply_roster(i + 1)
            if on_submit is not None:
                # scripted mid-run chaos seam (the swarmplan soak kills
                # and recovers a shard through it); awaited so the hook
                # can run kill/restart cycles inline with submission
                maybe_coro = on_submit(i + 1, hive)
                if asyncio.iscoroutine(maybe_coro):
                    await maybe_coro
        if kill_at is not None and not killed:
            await maybe_kill()
        await apply_roster(len(ordered))

        deadline = time.monotonic() + float(settle_timeout_s)
        while time.monotonic() < deadline:
            hive.sweep()
            done = sum(1 for job_id in issued
                       if job_id in hive.completed
                       or job_id in hive.abandoned)
            if done >= len(issued):
                break
            await asyncio.sleep(0.05)
    finally:
        duration_s = time.perf_counter() - t_start
        probe.stop()
        if auto_task is not None:
            auto_task.cancel()
            await asyncio.gather(auto_task, return_exceptions=True)
        for worker in workers:
            worker.request_stop()
        await asyncio.gather(*(asyncio.wait_for(t, timeout=30)
                               for t in tasks.values()),
                             return_exceptions=True)
        if auto_drains:
            await asyncio.gather(*auto_drains.values(),
                                 return_exceptions=True)
        await hive.stop()

    report = score_run(hive, issued, workers, ordered,
                       duration_s=duration_s)
    report["kill"] = killed or None
    # fleet-churn record (ISSUE 14 satellite): the roster satellite's
    # proof that /api/fleet (score_run's "fleet" stamp) and the
    # capacity model saw an ELASTIC fleet, not a static one
    report["roster"] = roster_events or None
    # measured host-contention factor (>= 1.0; ~1.0 idle). The gate's
    # contention-adjusted deadline clause scales its bound by this, so
    # a contended host loosens the bound by exactly the measured sleep
    # stretch — never by an arbitrary fudge.
    report["contention"] = probe.report()
    ad = report["admitted_deadline"]
    ad["p99_within_deadline_contention_adjusted"] = bool(
        ad["p99_latency_over_deadline"] <= probe.factor)
    # worker-hours ledger (swarmplan): the cost axis of the autoscaler
    # gate — stamped for EVERY run so static rosters and the elastic
    # fleet compare on identical accounting
    end_t = time.perf_counter()
    per_worker = {
        name: round(max(0.0, worker_stopped.get(name, end_t) - t0), 3)
        for name, t0 in sorted(worker_started.items())}
    total_s = sum(per_worker.values())
    report["worker_time"] = {
        "worker_seconds": round(total_s, 3),
        "worker_hours": round(total_s / 3600.0, 6),
        "peak_workers": len(per_worker),
        "per_worker": per_worker,
    }
    if autoscale is not None:
        report["autoscale"] = {
            "plan": dataclasses.asdict(autoscale),
            "events": auto_events,
            "sizes": auto_sizes,
            "ticks": planner.ticks,
            "decision": planner.last_decision,
            "drained": sorted(auto_drains),
        }
    else:
        report["autoscale"] = None
    return report


def _comparison_row(label: Any, report: dict[str, Any]) -> dict[str, Any]:
    """One row of the autoscaler comparison table: service quality
    (zero-loss, ok count, shed fraction, contention-adjusted deadline
    conformance) on one side, worker-hours on the other."""
    rec = report["reconciliation"]
    out = report["outcomes"]
    ad = report["admitted_deadline"]
    issued = max(1, int(rec["issued"]))
    return {
        "config": label,
        "zero_loss": bool(rec["zero_loss"]),
        "ok": int(out.get("ok", 0)),
        "shed_frac": round(int(out.get("shed", 0)) / issued, 4),
        "abandoned": int(out.get("abandoned", 0)),
        "p99_latency_over_deadline": ad["p99_latency_over_deadline"],
        "p99_ok": bool(ad["p99_within_deadline_contention_adjusted"]),
        "worker_seconds": report["worker_time"]["worker_seconds"],
        "worker_hours": report["worker_time"]["worker_hours"],
        "peak_workers": report["worker_time"]["peak_workers"],
    }


async def autoscale_comparison(schedule: Sequence[ScheduledJob], *,
                               autoscale: AutoscalePlan,
                               static_rosters: Sequence[int],
                               n_shards: int = 1,
                               seed: Any = "swarmplan",
                               shed_slack: float = 0.02,
                               **run_kwargs: Any) -> dict[str, Any]:
    """THE swarmplan headline (ISSUE 19 gate): drive the SAME seeded schedule once under the planner and
    once per static roster size, then compare worker-hours among the
    rosters that actually served the traffic.

    A static roster is **feasible** when it settles with zero loss, its
    admitted p99 sits within deadline (contention-adjusted, the PR-12
    clause), and its shed fraction is no worse than the planner's plus
    ``shed_slack`` — the last clause keeps a tiny roster that sheds
    half the peak from "winning" on hours while silently serving less
    traffic (shed fractions compare stably across host speeds, where
    raw ok counts wobble with planner ramp timing). The gate claim is:
    planner worker-hours STRICTLY below the cheapest feasible static
    roster, at equal-or-better service."""
    planner_report = await run_load(schedule, autoscale=autoscale,
                                    n_shards=n_shards, seed=seed,
                                    **run_kwargs)
    planner_row = _comparison_row("autoscaler", planner_report)
    static_rows: list[dict[str, Any]] = []
    for n in static_rosters:
        static_report = await run_load(schedule, n_workers=int(n),
                                       n_shards=n_shards,
                                       seed=f"{seed}-static{n}",
                                       **run_kwargs)
        static_rows.append(_comparison_row(int(n), static_report))
    feasible = [row for row in static_rows
                if row["zero_loss"] and row["p99_ok"]
                and row["shed_frac"]
                <= planner_row["shed_frac"] + float(shed_slack)]
    best_static = (min(feasible, key=lambda r: r["worker_seconds"])
                   if feasible else None)
    gate = {
        "planner_zero_loss": planner_row["zero_loss"],
        "planner_p99_ok": planner_row["p99_ok"],
        "feasible_static": sorted(r["config"] for r in feasible),
        "best_static": (best_static or {}).get("config"),
        "best_static_worker_seconds":
            (best_static or {}).get("worker_seconds"),
        "planner_worker_seconds": planner_row["worker_seconds"],
        "planner_beats_best_static": bool(
            best_static is not None
            and planner_row["worker_seconds"]
            < best_static["worker_seconds"]),
    }
    return {
        "planner": planner_row,
        "static": static_rows,
        "gate": gate,
        "planner_report": planner_report,
    }


# ---------------------------------------------------------------------------
# scoring + the capacity model
# ---------------------------------------------------------------------------


def reconcile(hive: MiniHive, issued: Iterable[str]) -> dict[str, Any]:
    """THE zero-loss check: every issued job settled exactly once —
    completed (success or final error envelope) XOR abandoned-by-policy
    — and the settle lists carry no duplicates. Shared by the scorer,
    the acceptance gate, and the reconciliation tests."""
    issued = [str(j) for j in issued]
    completed = set(hive.completed)
    abandoned = set(hive.abandoned)
    uploaded = hive.uploaded_ids()
    missing = [j for j in issued if j not in completed
               and j not in abandoned]
    double = [j for j in issued if j in completed and j in abandoned]
    return {
        "issued": len(issued),
        "completed": len([j for j in issued if j in completed]),
        "abandoned": len([j for j in issued if j in abandoned]),
        "duplicate_uploads_acked": len(hive.duplicate_results),
        "missing": missing,
        "settled_twice": double,
        "result_list_unique": len(uploaded) == len(set(uploaded)),
        "zero_loss": (not missing and not double
                      and len(uploaded) == len(set(uploaded))),
    }


def _worker_snapshot(worker: Any) -> dict[str, Any]:
    stats = worker.stats.snapshot()
    stepper = worker._stepper_health()
    breakers = worker.breakers.states()
    snap = {
        "jobs_shed": stats.get("jobs_shed", 0),
        "polls_backpressured": stats.get("polls_backpressured", 0),
        "jobs_failed": stats.get("jobs_failed", 0),
        "jobs_timed_out": stats.get("jobs_timed_out", 0),
        "lane_occupancy": stepper.get("lane_occupancy", 0.0),
        "padding_waste": stepper.get("padding_waste", 0.0),
        "lane_resizes": stepper.get("lane_resizes", 0),
        "breaker_trips": sum(1 for b in breakers.values()
                             if b.get("state") != "closed"),
        "overload": worker.overload.snapshot(),
    }
    residency = getattr(worker.registry, "residency", None)
    if residency is not None:
        try:
            r = residency.snapshot()
            snap["residency"] = {
                "resident_models": len(r.get("resident_models", [])),
                "resident_bytes": r.get("resident_bytes", 0),
                "evictions": r.get("evictions", 0),
            }
        except Exception:  # stub registries
            pass
    return snap


def score_run(hive: LoadHive, issued: Sequence[str], workers: Sequence[Any],
              schedule: Sequence[ScheduledJob], *,
              duration_s: float) -> dict[str, Any]:
    """Fold one run into the report: settlement reconciliation, outcome
    buckets, per-workload latency percentiles, admitted-deadline
    conformance, worker snapshots, and the capacity model."""
    workload_by_id = {str(s.job["id"]): s.workload for s in schedule}
    deadline_by_id = {str(s.job["id"]): float(s.job.get("deadline_s") or 0)
                      for s in schedule}
    family_by_id = {str(s.job["id"]): model_family(s.job.get("model_name"))
                    for s in schedule}
    family_latencies: dict[str, list[float]] = {}
    # deadline-budget attribution (swarmsight, ISSUE 13): per-family
    # phase decompositions folded from the hive's flight records — one
    # bucket over every completed job, one over the deadline MISSES so
    # the conformance report can name the dominant overshoot phase
    flights = getattr(hive, "flights", None)
    fam_attr: dict[str, dict[str, list[float]]] = {}
    fam_miss_attr: dict[str, dict[str, list[float]]] = {}
    outcomes = {"ok": 0, "shed": 0, "abandoned": len(hive.abandoned)}
    end_to_end: dict[str, list[float]] = {}
    admitted: dict[str, list[float]] = {}
    deadline_ratios: list[float] = []
    deadline_violations: list[str] = []
    admitted_latencies: list[float] = []
    for job_id, result in hive.completed.items():
        kind = classify_result(result)
        if kind == "ok":
            outcomes["ok"] += 1
        elif kind == "overloaded":
            outcomes["shed"] += 1
        else:
            outcomes[kind] = outcomes.get(kind, 0) + 1
        workload = workload_by_id.get(job_id, "unknown")
        submitted = hive.submitted_at.get(job_id)
        granted = hive.granted_at.get(job_id)
        settled = hive.settled_at.get(job_id)
        if settled is None:
            continue
        if submitted is not None:
            end_to_end.setdefault(workload, []).append(settled - submitted)
        if kind != "ok":
            continue
        if granted is not None:
            latency = settled - granted
            admitted.setdefault(workload, []).append(latency)
            admitted_latencies.append(latency)
        if submitted is not None:
            family = family_by_id.get(job_id, "sd15")
            family_latencies.setdefault(family, []).append(
                settled - submitted)
            attribution = None
            if flights is not None:
                record = flights.get(job_id)
                attribution = (record or {}).get("attribution")
            if attribution:
                bucket = fam_attr.setdefault(family, {})
                for phase, seconds in attribution["phases"].items():
                    bucket.setdefault(phase, []).append(float(seconds))
            # deadline conformance is END TO END (submit -> settle):
            # queue age rides every delivery as "queued_s", so a worker
            # that admits a stale job owns the whole budget it spent.
            # Pooled as latency/deadline RATIOS: workloads carry
            # different deadlines, and the ratio normalizes them into
            # ONE p99 over all admitted jobs (per-workload p99 with a
            # handful of samples degenerates to the max).
            e2e = settled - submitted
            deadline = deadline_by_id.get(job_id, 0.0)
            if deadline:
                deadline_ratios.append(e2e / deadline)
                if e2e > deadline:
                    deadline_violations.append(job_id)
                    if attribution:
                        miss = fam_miss_attr.setdefault(family, {})
                        for phase, seconds in \
                                attribution["phases"].items():
                            miss.setdefault(phase, []).append(
                                float(seconds))

    def fold(samples: dict[str, list[float]]) -> dict[str, dict]:
        return {w: {"p50": round(percentile(v, 0.50), 4),
                    "p99": round(percentile(v, 0.99), 4),
                    "n": len(v)}
                for w, v in sorted(samples.items())}

    def attribution_table(samples: dict[str, dict[str, list[float]]]
                          ) -> dict[str, dict]:
        """Per-family budget-attribution table: mean seconds + share
        per phase, plus the argmax phase (ISSUE 13)."""
        table: dict[str, dict] = {}
        for family, phases in sorted(samples.items()):
            mean = {phase: round(sum(vals) / max(1, len(vals)), 4)
                    for phase, vals in sorted(phases.items())}
            total = sum(mean.values())
            table[family] = {
                "n": max((len(v) for v in phases.values()), default=0),
                "mean_s": mean,
                "share": {phase: round(v / total, 4) if total else 0.0
                          for phase, v in mean.items()},
                # None when nothing was measured: an argmax over
                # all-zero means would crown the first phase and send
                # an operator chasing a queue that never dominated
                "dominant_phase": (max(
                    ATTRIBUTION_PHASES,
                    key=lambda p: mean.get(p, 0.0)) if total else None),
            }
        return table

    mix: dict[str, int] = {}
    for item in schedule:
        mix[item.workload] = mix.get(item.workload, 0) + 1
    chips = sum(int(getattr(slot, "data_width", 1) or 1)
                for worker in workers for slot in worker.pool)
    models_resident = 0
    for worker in workers:
        residency = getattr(worker.registry, "residency", None)
        if residency is not None:
            try:
                models_resident = max(
                    models_resident,
                    len(residency.snapshot().get("resident_models", [])))
            except Exception:
                pass
    if not models_resident:
        models_resident = len({s.job.get("model_name")
                               for s in schedule})
    completed_ok = outcomes["ok"]
    duration_s = max(1e-6, float(duration_s))
    report = {
        "reconciliation": reconcile(hive, issued),
        "outcomes": outcomes,
        "offered": {
            "jobs": len(schedule),
            "duration_s": round(duration_s, 3),
            "rate_jobs_s": round(len(schedule) / duration_s, 3),
            "workload_mix": {w: round(n / max(1, len(schedule)), 4)
                             for w, n in sorted(mix.items())},
        },
        "latency_s": {
            "end_to_end": fold(end_to_end),
            "admitted": fold(admitted),
        },
        "admitted_deadline": {
            "violations": len(deadline_violations),
            "violating_ids": deadline_violations[:10],
            # THE acceptance bound: p99 of end-to-end latency/deadline
            # over every ADMITTED (completed-ok) job must sit at <= 1
            "p99_latency_over_deadline": round(
                percentile(deadline_ratios, 0.99), 4),
            "p99_within_deadline":
                percentile(deadline_ratios, 0.99) <= 1.0,
        },
        # per-model-family deadline derivation (ISSUE 10 satellite,
        # ROADMAP 5b): measured p99 of completed-ok end-to-end latency
        # per family x the margin — the table an operator copies into
        # the ``family_deadline_s`` settings map. The SHIPPED defaults
        # come from the pure sweep (sweep_deadline_table, pinned by
        # test); this is the live-measurement refinement of them.
        "suggested_deadlines": {
            "margin": DEADLINE_MARGIN,
            "families": {
                family: {
                    "p99_s": round(percentile(values, 0.99), 4),
                    "suggested_s": round(
                        percentile(values, 0.99) * DEADLINE_MARGIN, 4),
                    "n": len(values),
                }
                for family, values in sorted(family_latencies.items())
            },
        },
        # measured watchdog-knob suggestion (swarmlens, ISSUE 11): from
        # the process-global step-seconds histogram — populated by runs
        # that drive REAL lanes (the nightly real-lane soak); synthetic
        # executors step no lanes, so those runs report measured=False
        # rather than inventing numbers from simulated service times
        "suggested_hang_budget": _suggest_hang_budget(),
        # per-family deadline-BUDGET attribution (swarmsight, ISSUE 13):
        # where each family's end-to-end seconds actually went, folded
        # from the flight records; misses get their own table so a p99
        # overshoot names a phase, not just a number
        "budget_attribution": {
            "families": attribution_table(fam_attr),
            "misses": attribution_table(fam_miss_attr),
        },
        # the /api/fleet aggregate at scoring time — the observed data
        # plane (arrival rates, occupancy, chips, residency, overload)
        # the ROADMAP item-5 autoscaler consumes
        "fleet": (hive.fleet_snapshot()
                  if hasattr(hive, "fleet_snapshot") else None),
        "workers": {w.settings.worker_name: _worker_snapshot(w)
                    for w in workers},
        "hive": hive.stats(),
        "capacity": {
            "chips": chips,
            "jobs_per_s_per_chip": round(
                completed_ok / duration_s / max(1, chips), 4),
            "admitted_p99_s": round(percentile(admitted_latencies, 0.99),
                                    4),
            "models_resident": models_resident,
            "workload_mix": {w: round(n / max(1, len(schedule)), 4)
                             for w, n in sorted(mix.items())},
        },
    }
    # the deadline-conformance satellite (ISSUE 13): each family's p99
    # miss points at a PHASE — the miss-table argmax rides next to the
    # suggested deadline so "raise the budget" and "fix the phase" are
    # distinguishable actions
    for family, entry in report["suggested_deadlines"]["families"].items():
        miss = report["budget_attribution"]["misses"].get(family)
        entry["dominant_overshoot_phase"] = (miss["dominant_phase"]
                                             if miss else None)
    return report


# ---------------------------------------------------------------------------
# tuning sweeps (pure host simulation — the harness's arrival model
# replayed through the controllers; no jax, fully deterministic)
# ---------------------------------------------------------------------------


def arrival_trace(curve: DiurnalCurve, *, boundaries: int,
                  mean_rows: float, seed: Any) -> list[int]:
    """Rows arriving at each of ``boundaries`` step boundaries: seeded
    Poisson draws scaled by the curve — the discrete twin of
    :func:`generate_schedule` at lane-step resolution."""
    rng = random.Random(f"trace:{seed}")
    out = []
    for b in range(max(1, int(boundaries))):
        lam = mean_rows * curve.multiplier(b / max(1, boundaries - 1))
        # inverse-CDF Poisson (stdlib-only, fine for small lambda)
        x, p, s = 0, math.exp(-lam), math.exp(-lam)
        u = rng.random()
        while u > s and x < 1000:
            x += 1
            p *= lam / x
            s += p
        out.append(x)
    return out


def simulate_lane_controller(*, grow_at: float, shrink_at: float,
                             patience: int, trace: Sequence[int],
                             steps_per_row: int = 12,
                             max_width: int = 16) -> dict[str, float]:
    """Replay one arrival trace through a synthetic lane driven by
    :class:`~chiaswarm_tpu.serving.stepper.LaneWidthController`:
    rows admitted up to the width each boundary run ``steps_per_row``
    boundaries, the controller decides between dispatches. Scored on
    the two costs the gains trade off — padded row-steps (batched UNet
    FLOPs burned) and queue wait (rows x boundaries spent pending)."""
    from chiaswarm_tpu.serving.stepper import LaneWidthController

    ctl = LaneWidthController(min_width=1, max_width=max_width,
                              grow_at=grow_at, shrink_at=shrink_at,
                              patience=patience)
    width = 1                  # a lane opens at its first job's bucket
    resident: list[int] = []   # remaining steps per occupied row
    pending = 0
    padded = active = waited = resizes = 0
    for b, arriving in enumerate(list(trace) + [0] * steps_per_row):
        pending += int(arriving)
        free = width - len(resident)
        admit = min(pending, free)
        resident.extend([steps_per_row] * admit)
        pending -= admit
        if resident:
            active += len(resident)
            padded += width - len(resident)
            resident = [r - 1 for r in resident if r > 1]
        waited += pending
        target = ctl.decide(width, len(resident), pending, float(arriving))
        if target != width:
            resizes += 1
            width = target
    denom = max(1, active + padded)
    return {
        "padding_waste": round(padded / denom, 4),
        "queue_wait_row_steps": waited,
        "resizes": resizes,
        # one scalar to rank by: padding plus normalized wait (a padded
        # row-step and a waited row-step burn comparable wall time)
        "cost": round(padded / denom + waited / denom, 4),
    }


#: relative cost below which the lane-gain sweep does not tell two grid
#: points apart. The three patience values of the winning (grow_at,
#: shrink_at) pair have always lain within 0.03% of each other (PR 9:
#: 50.0481 / 50.0499 / 50.0526; since ISSUE 27's 2 -> 1 shrink:
#: 50.0358 / 50.0478 / 50.0539, patience 2 ahead), while the nearest
#: other pair is 0.14% behind — 0.1% separates pairs, not patience
LANE_SWEEP_RESOLUTION = 0.001


def sweep_lane_gains(seed: Any = "swarmload",
                     grid: Sequence[tuple[float, float, int]] | None = None,
                     panel: int = 4) -> dict[str, Any]:
    """Score LaneWidthController gain triples over the harness's three
    canonical regimes (steady trickle, diurnal, spiky burst), each
    replayed over a ``panel`` of seed-derived traces so one lucky trace
    cannot crown a winner. The shipped defaults are asserted against the default-seed
    winner in tests/test_loadgen.py so a default and the harness can
    never silently disagree."""
    if grid is None:
        grid = [(g, s, p)
                for g in (0.625, 0.75, 0.875)
                for s in (0.25, 0.375)
                for p in (2, 4, 6)]
    regimes = {}
    for k in range(max(1, int(panel))):
        regimes[f"trickle:{k}"] = arrival_trace(
            DiurnalCurve(amplitude=0.2, spikes=0, seed=f"{seed}:{k}"),
            boundaries=600, mean_rows=0.15, seed=f"{seed}:trickle:{k}")
        regimes[f"diurnal:{k}"] = arrival_trace(
            DiurnalCurve(amplitude=0.7, spikes=1, seed=f"{seed}:{k}"),
            boundaries=600, mean_rows=0.5, seed=f"{seed}:diurnal:{k}")
        regimes[f"burst:{k}"] = arrival_trace(
            DiurnalCurve(amplitude=0.4, spikes=3, spike_mult=6.0,
                         seed=f"{seed}:{k}"),
            boundaries=600, mean_rows=0.8, seed=f"{seed}:burst:{k}")
    results = []
    for grow_at, shrink_at, patience in grid:
        scores = {name: simulate_lane_controller(
            grow_at=grow_at, shrink_at=shrink_at, patience=patience,
            trace=trace) for name, trace in regimes.items()}
        by_regime: dict[str, float] = {}
        for name, score in scores.items():
            regime = name.split(":", 1)[0]
            by_regime[regime] = round(
                by_regime.get(regime, 0.0) + score["cost"], 4)
        results.append({
            "grow_at": grow_at, "shrink_at": shrink_at,
            "patience": patience,
            "cost": round(sum(s["cost"] for s in scores.values()), 4),
            "cost_by_regime": by_regime,
            "resizes": sum(s["resizes"] for s in scores.values()),
        })
    results.sort(key=lambda r: (r["cost"], r["grow_at"], r["shrink_at"],
                                r["patience"]))
    winner = results[0]
    from chiaswarm_tpu.serving.stepper import LaneWidthController

    defaults = LaneWidthController()
    shipped = next(
        (r for r in results
         if (r["grow_at"], r["shrink_at"], r["patience"])
         == (defaults.grow_at, defaults.shrink_at, defaults.patience)),
        None)
    gap = (None if shipped is None
           else round(shipped["cost"] / winner["cost"] - 1.0, 6))
    return {
        "winner": {k: winner[k] for k in
                   ("grow_at", "shrink_at", "patience", "cost")},
        "defaults": {"grow_at": defaults.grow_at,
                     "shrink_at": defaults.shrink_at,
                     "patience": defaults.patience},
        # how far the shipped triple costs over the winner (0 = it IS
        # the winner). The gain pair must be the winner's; patience
        # only to within what this score can tell apart: it charges a
        # resize nothing, so it cannot price what patience is for
        "defaults_cost_gap": gap,
        "defaults_match_winner": (
            gap is not None and gap <= LANE_SWEEP_RESOLUTION
            and (defaults.grow_at, defaults.shrink_at)
            == (winner["grow_at"], winner["shrink_at"])),
        "table": results,
    }


def simulate_prefetch(window_s: float, *, models: int = 4,
                      events: int = 400, seed: Any = "swarmload",
                      ) -> dict[str, float]:
    """Score one ArrivalEwma window as the prefetch ranking signal:
    a one-free-slot cache prefetches the top-ranked non-resident model
    between accesses; hit rate over a seeded stream with per-model
    periodicity + regime shifts (the pattern the ranking must track —
    too short a window chases noise, too long one lags the shift)."""
    from chiaswarm_tpu.serving.residency import ArrivalEwma

    rng = random.Random(f"prefetch:{seed}")
    # per-model base weights, re-drawn mid-stream (the regime shift)
    weights = [rng.uniform(0.5, 2.0) for _ in range(models)]
    ewmas = [ArrivalEwma(window_s=window_s) for _ in range(models)]
    resident: set[int] = {0}
    capacity = max(1, models // 2)
    now = 0.0
    hits = misses = 0
    for event in range(max(1, int(events))):
        if event == events // 2:
            weights = [rng.uniform(0.5, 2.0) for _ in range(models)]
        now += rng.expovariate(1.0)
        model = rng.choices(range(models), weights=weights)[0]
        ewmas[model].note(1, now)
        if model in resident:
            hits += 1
        else:
            misses += 1
            resident.add(model)
            if len(resident) > capacity:   # LRU-free stand-in: evict
                resident.discard(min(     # the coldest by the EWMA
                    (m for m in resident if m != model),
                    key=lambda m: ewmas[m].rate(now)))
        # idle prefetch: warm the hottest non-resident model
        if len(resident) < capacity:
            candidates = [m for m in range(models) if m not in resident]
            if candidates:
                resident.add(max(candidates,
                                 key=lambda m: ewmas[m].rate(now)))
    return {"window_s": window_s,
            "hit_rate": round(hits / max(1, hits + misses), 4)}


def sweep_prefetch_window(seed: Any = "swarmload",
                          windows: Sequence[float] = (5.0, 10.0, 20.0,
                                                      40.0),
                          panel: int = 6) -> dict[str, Any]:
    """Rank candidate ArrivalEwma windows for the residency prefetch
    ranking (ISSUE 9 satellite: tune prefetch aggressiveness from
    harness sweeps), averaged over a ``panel`` of seed-derived streams.
    The shipped value
    is ``serving.residency.PREFETCH_RANK_WINDOW_S`` — deliberately
    separate from the lane demand EWMA's short window (model reuse has
    minutes-scale locality, lane demand has seconds-scale)."""
    from chiaswarm_tpu.serving.residency import PREFETCH_RANK_WINDOW_S

    table = []
    for window in windows:
        runs = [simulate_prefetch(window, seed=f"{seed}:{k}")
                for k in range(max(1, int(panel)))]
        table.append({
            "window_s": window,
            "hit_rate": round(sum(r["hit_rate"] for r in runs)
                              / len(runs), 4),
        })
    winner = max(table, key=lambda r: (r["hit_rate"], -r["window_s"]))
    return {
        "winner": winner,
        "default_window_s": PREFETCH_RANK_WINDOW_S,
        "defaults_match_winner":
            PREFETCH_RANK_WINDOW_S == winner["window_s"],
        "table": table,
    }


# ---------------------------------------------------------------------------
# operator entry point
# ---------------------------------------------------------------------------


def build_scenario(*, seed: Any, n_users: int, duration_s: float,
                   rate_jobs_s: float,
                   profiles: Sequence[WorkloadProfile] = DEFAULT_PROFILES,
                   models: Sequence[str] = ("swarm/sd15",),
                   ) -> list[ScheduledJob]:
    population = UserPopulation(n_users=n_users, profiles=profiles,
                                models=models, seed=seed)
    curve = DiurnalCurve(seed=seed)
    return generate_schedule(population, curve, duration_s=duration_s,
                             rate_jobs_s=rate_jobs_s, seed=seed,
                             id_prefix=f"load-{seed}")


def main() -> None:  # `python -m chiaswarm_tpu.node.loadgen`
    """Operator smoke: a seeded diurnal scenario against synthetic
    overload-controlled workers, JSON report on stdout. Knobs:
    CHIASWARM_LOAD_SEED / _USERS / _DURATION_S / _RATE / _WORKERS /
    _KILL (1 = kill a worker mid-run)."""
    seed = os.environ.get("CHIASWARM_LOAD_SEED", "swarmload")
    schedule = build_scenario(
        seed=seed,
        n_users=int(os.environ.get("CHIASWARM_LOAD_USERS", "2000")),
        duration_s=float(os.environ.get("CHIASWARM_LOAD_DURATION_S",
                                        "10")),
        rate_jobs_s=float(os.environ.get("CHIASWARM_LOAD_RATE", "20")))
    kill = (KillPlan() if os.environ.get("CHIASWARM_LOAD_KILL", "")
            .strip().lower() in ("1", "true", "on", "yes") else None)
    report = asyncio.run(run_load(
        schedule,
        n_workers=int(os.environ.get("CHIASWARM_LOAD_WORKERS", "3")),
        kill=kill, seed=seed))
    report["sweeps"] = {
        "lane_gains": sweep_lane_gains(seed),
        "prefetch_window": sweep_prefetch_window(seed),
    }
    print(json.dumps(report))


if __name__ == "__main__":
    main()
