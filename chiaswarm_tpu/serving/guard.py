"""swarmguard: gray-failure detection and the self-healing ladder.

The fleet survives clean deaths (PR-6 leases + checkpoint/resume),
crashes and OOMs (the PR-2 ladder), and overload (PR-9 shedding) — but a
worker that *degrades without dying* was invisible until this module: a
wedged compiled step holds a lane's rows hostage until the per-row
deadline, a NaN-poisoned trajectory uploads garbage images that settle
as "completed", and a sick device drags every lane on it. This is the
classic gray-failure gap of serving systems; the node must detect its
own sickness and heal in place, not just die loudly. Three mechanisms:

- **In-flight step watchdog**: a monitor thread (:class:`Watchdog`)
  arms a wall-clock budget per compiled call — ``k x`` the lane
  scheduler's step-seconds EWMA, clamped between floor and ceiling
  knobs — around lane step dispatches (serving/stepper.py) and solo
  denoise phases (node/executor.py ``watch_solo``). A call that
  outlives its budget is declared HUNG: the lane is condemned
  (:meth:`~chiaswarm_tpu.serving.stepper.Lane.condemn`) and its rows
  are re-admitted to a freshly built lane, resuming from the last
  step-boundary checkpoint; a hung solo phase raises :class:`StepHung`
  (classified ``transient``) once the call returns, so the PR-2 ladder
  re-runs it.
- **Per-row output validation**: a finite-check on the lane latents
  rides the existing checkpoint-boundary device->host transfer, and
  :func:`screen_images` screens decoded frames for NaN/Inf and
  constant (black) frames. A poisoned row retires with a structured
  non-fatal ``invalid_output`` envelope — a
  :data:`~chiaswarm_tpu.node.resilience.REDISPATCH_KINDS` member and
  breaker fodder — instead of uploading garbage, and never takes its
  lane peers down.
- **Device-health scorer + healing ladder** (:class:`DeviceGuard`):
  consecutive hangs / slow steps / invalid outputs per device feed a
  health score; rungs escalate lane-rebuild (intrinsic to every
  condemnation) -> executable-cache flush
  (``core/compile_cache.py::CompileCache.flush_executables``) ->
  device quarantine (the worker shrinks the slot mesh to the healthy
  chips and re-advertises capacity on /healthz) -> self-restart
  request (graceful PR-2 drain with :data:`GUARD_RESTART_EXIT_CODE`
  so supervisors distinguish "restart me" from a crash).

Chaos seams (deterministic, like the PR-2/PR-3 harnesses):

- ``CHIASWARM_CHAOS_WEDGE_STEP="N:S"``   sleep S seconds inside lane
  step N's armed window — the wedged-compiled-call stand-in (one shot
  process-wide; the first lane to reach step N consumes it)
- ``CHIASWARM_CHAOS_SLOW_STEP="M"``      stretch every lane step to
  ~M x its own wall time (the sick-but-alive device)
- ``CHIASWARM_CHAOS_NAN_STEP="T:R"``     poison lane row R with NaN
  after step T (one shot) — proves the validation rung

Watchdog/validation knobs (env, like the stepper's):

- ``CHIASWARM_GUARD=0``               disable watchdog + validation
- ``CHIASWARM_GUARD_HANG_FACTOR``     budget = factor x step EWMA (20)
- ``CHIASWARM_GUARD_HANG_FLOOR_S``    budget floor, seconds (30)
- ``CHIASWARM_GUARD_HANG_CEIL_S``     budget ceiling — also the cold
  budget while no EWMA exists, so a first-call compile is never
  condemned (600)
- ``CHIASWARM_GUARD_SLOW_FACTOR``     a step slower than factor x the
  EWMA counts as a slow-step health event (4)

Ladder thresholds are worker settings (``guard_*``, node/settings.py);
the rung state surfaces as ``chiaswarm_guard_*`` metric families
(obs/metrics.py) and the ``/healthz`` ``guard`` key.

Stdlib + numpy only — importable without jax, like node/resilience.py,
so the chaos suite and unit tests load it anywhere.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import logging
import os
import threading
import time
from typing import Any, Callable, Iterable

import numpy as np

from chiaswarm_tpu.obs import metrics as obs_metrics

log = logging.getLogger("chiaswarm.guard")

#: exit code a guard-requested self-restart leaves behind (after the
#: graceful PR-2 drain): supervisors restart-on-73 instead of paging
GUARD_RESTART_EXIT_CODE = 73

ENV_ENABLE = "CHIASWARM_GUARD"
ENV_HANG_FACTOR = "CHIASWARM_GUARD_HANG_FACTOR"
ENV_HANG_FLOOR = "CHIASWARM_GUARD_HANG_FLOOR_S"
ENV_HANG_CEIL = "CHIASWARM_GUARD_HANG_CEIL_S"
ENV_SLOW_FACTOR = "CHIASWARM_GUARD_SLOW_FACTOR"

ENV_CHAOS_WEDGE = "CHIASWARM_CHAOS_WEDGE_STEP"
ENV_CHAOS_SLOW = "CHIASWARM_CHAOS_SLOW_STEP"
ENV_CHAOS_NAN = "CHIASWARM_CHAOS_NAN_STEP"


# ---------------------------------------------------------------------------
# failure vocabulary
# ---------------------------------------------------------------------------


class StepHung(RuntimeError):
    """A watched solo phase outlived its hang budget. Raised AFTER the
    wedged call finally returns (a blocked thread cannot be interrupted;
    one that never returns is the PR-2 deadline envelope's job) and
    classified ``transient`` so the ladder re-runs the job."""


class LaneHung(RuntimeError):
    """A condemned lane failed this job's rows. ``resume`` carries the
    last in-memory step-boundary checkpoint (the PR-6 lane state shape)
    or None; the executor re-admits the job to a freshly built lane,
    resuming at the checkpointed step when one exists."""

    def __init__(self, message: str,
                 resume: dict[str, Any] | None = None) -> None:
        super().__init__(message)
        self.resume = resume


class InvalidOutput(RuntimeError):
    """A row's trajectory is numerically poisoned (non-finite latents,
    NaN/Inf or constant decoded frames). The job retires with a
    non-fatal ``invalid_output`` envelope — never an uploaded garbage
    image — and a lease-aware hive redispatches it elsewhere."""


def watchdog_enabled() -> bool:
    """The guard (watchdog + output validation) is ON by default;
    ``CHIASWARM_GUARD=0`` opts the node out entirely."""
    return os.environ.get(ENV_ENABLE, "").strip().lower() not in (
        "0", "false", "off", "no")


def validation_enabled() -> bool:
    return watchdog_enabled()


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def hang_budget_s(step_ewma: float) -> float:
    """Wall-clock budget for one compiled lane step: ``factor x`` the
    scheduler's step-seconds EWMA, clamped to [floor, ceiling]. With no
    EWMA yet (the lane's first call — which COMPILES) the ceiling is
    the budget, so a legitimate cold compile is never condemned."""
    factor = _env_float(ENV_HANG_FACTOR, 20.0)
    floor = _env_float(ENV_HANG_FLOOR, 30.0)
    ceil = max(floor, _env_float(ENV_HANG_CEIL, 600.0))
    if step_ewma <= 0.0:
        return ceil
    return min(ceil, max(floor, factor * float(step_ewma)))


def solo_hang_budget_s(step_ewma: float, steps: int) -> float | None:
    """Budget for a whole solo denoise phase (``steps`` x the lane step
    EWMA x factor). None — never armed — when there is no EWMA evidence
    or no step count: a cold solo path must not false-positive on its
    own compile."""
    if step_ewma <= 0.0 or int(steps or 0) <= 0:
        return None
    factor = _env_float(ENV_HANG_FACTOR, 20.0)
    floor = _env_float(ENV_HANG_FLOOR, 30.0)
    ceil = max(floor, _env_float(ENV_HANG_CEIL, 600.0))
    return min(ceil, max(floor, factor * float(step_ewma) * int(steps)))


def slow_factor() -> float:
    return max(1.0, _env_float(ENV_SLOW_FACTOR, 4.0))


#: minimum step-seconds observations before a hang-budget suggestion is
#: called MEASURED — below this the histogram is warm-up noise
SUGGEST_MIN_SAMPLES = 32


def suggest_hang_budget(histogram: Any = None, *,
                        min_samples: int = SUGGEST_MIN_SAMPLES) -> dict:
    """MEASURED watchdog-knob suggestion from the live step-seconds
    histogram (swarmlens, ISSUE 11) — closes the PR-10 carry-over that
    factor 20 / floor 30 s / ceiling 600 s are priors, not measurements.

    Derivation (documented so operators can audit the numbers):

    - ``factor``  = 4x the measured p99/p50 dispersion, clamped to
      [4, 20] — the budget tracks the EWMA, so the factor only needs to
      absorb step-to-step variance plus headroom, not absolute scale.
    - ``floor_s`` = 20x p99, at least 1 s — guards the budget when the
      EWMA is tiny (fast lanes), so scheduler jitter cannot condemn.
    - ``ceil_s``  = 200x p99 bounded to [60 s, the configured ceiling]
      — the worst legitimate warm step; cold COMPILES are exempt from
      this bound by construction (the watchdog gives un-warmed
      dispatches the ceiling alone, so the ceiling need not cover
      compile time, only pathological-but-alive steps).

    Returns ``{"measured": False, "samples": n}`` until ``min_samples``
    observations exist; /healthz and the loadgen report both
    stamp this payload, so a real TPU deployment reads its knobs off
    its own histogram.
    """
    if histogram is None:
        from chiaswarm_tpu.obs.metrics import REGISTRY

        histogram = REGISTRY.get("chiaswarm_stepper_step_seconds")
    current = {
        "factor": _env_float(ENV_HANG_FACTOR, 20.0),
        "floor_s": _env_float(ENV_HANG_FLOOR, 30.0),
        "ceil_s": max(_env_float(ENV_HANG_FLOOR, 30.0),
                      _env_float(ENV_HANG_CEIL, 600.0)),
    }
    samples = histogram.count() if histogram is not None else 0
    if histogram is None or samples < min_samples:
        return {"measured": False, "samples": int(samples),
                "min_samples": int(min_samples), "current": current}
    p50 = histogram.percentile(0.5)
    p99 = histogram.percentile(0.99)
    if not p50 or not p99:
        return {"measured": False, "samples": int(samples),
                "min_samples": int(min_samples), "current": current}
    dispersion = max(1.0, p99 / p50)
    factor = min(20.0, max(4.0, 4.0 * dispersion))
    floor_s = max(1.0, 20.0 * p99)
    ceil_s = min(current["ceil_s"], max(60.0, 200.0 * p99))
    return {
        "measured": True,
        "samples": int(samples),
        "p50_s": round(p50, 6),
        "p99_s": round(p99, 6),
        "suggested": {
            "factor": round(factor, 2),
            "floor_s": round(floor_s, 3),
            "ceil_s": round(max(ceil_s, floor_s), 3),
        },
        "current": current,
    }


# ---------------------------------------------------------------------------
# the watchdog monitor thread
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Armed:
    deadline: float
    on_hang: Callable[[], None]
    tag: str
    fired: bool = False


class Watchdog:
    """One monitor thread declaring in-flight compiled calls hung.

    ``arm(budget, on_hang)`` registers a deadline; ``disarm(ticket)``
    withdraws it and reports whether it fired. Fire-vs-disarm races
    resolve under the watchdog lock: a disarmed ticket can never fire
    afterwards, and a fired one reports ``True`` to its disarmer. The
    ``on_hang`` callback runs in the MONITOR thread and must never
    block on the device — the wedged dispatch is exactly what it
    cannot wait on."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._armed: dict[int, _Armed] = {}
        self._ids = itertools.count(1)
        self._thread: threading.Thread | None = None

    def arm(self, budget_s: float, on_hang: Callable[[], None],
            tag: str = "") -> int:
        ticket = next(self._ids)
        entry = _Armed(time.monotonic() + float(budget_s), on_hang, tag)
        with self._cond:
            self._armed[ticket] = entry
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._monitor, name="swarmguard-watchdog",
                    daemon=True)
                self._thread.start()
            self._cond.notify_all()
        return ticket

    def disarm(self, ticket: int) -> bool:
        """Withdraw ``ticket``; True when it already fired (the caller
        was declared hung while it was away)."""
        with self._cond:
            entry = self._armed.pop(ticket, None)
        return bool(entry is not None and entry.fired)

    def _monitor(self) -> None:
        while True:
            with self._cond:
                now = time.monotonic()
                due = [e for e in self._armed.values()
                       if not e.fired and e.deadline <= now]
                for entry in due:
                    entry.fired = True
                pending = [e.deadline for e in self._armed.values()
                           if not e.fired]
                timeout = (min(pending) - now) if pending else 60.0
            for entry in due:
                log.error("watchdog: %s exceeded its hang budget; "
                          "declaring it hung", entry.tag or "a call")
                try:
                    entry.on_hang()
                except Exception:  # a broken heal hook must not kill
                    log.exception("watchdog on_hang callback failed "
                                  "for %s", entry.tag)
            with self._cond:
                self._cond.wait(timeout=max(0.005, min(timeout, 60.0)))


#: process-wide watchdog (lane drivers + solo phases share the monitor)
WATCHDOG = Watchdog()

# executable-cache flush epoch: the cache_flush heal rung bumps this
# (node/worker.py), and every lane treats its next dispatch as COLD —
# budgeted at the ceiling — because that dispatch recompiles. Without
# it the flush rung would manufacture its own "hangs" out of the very
# recompiles it caused and self-amplify up the ladder.
_FLUSH_LOCK = threading.Lock()
_FLUSH_EPOCH = 0


def flush_epoch() -> int:
    with _FLUSH_LOCK:
        return _FLUSH_EPOCH


def note_cache_flush() -> None:
    """Record that the executable cache was flushed (the heal rung):
    in-flight lanes re-enter their cold-budget window."""
    global _FLUSH_EPOCH
    with _FLUSH_LOCK:
        _FLUSH_EPOCH += 1


def _slot_devices(slot: Any) -> list[str]:
    """Device labels of one mesh slot (stub slots report nothing)."""
    mesh = getattr(slot, "mesh", None)
    if mesh is None:
        return []
    try:
        return [str(d.id) for d in mesh.devices.flatten()]
    except Exception:  # exotic mesh stubs
        return []


@contextlib.contextmanager
def watch_solo(slot: Any, steps: Any, key: Any = None):
    """Arm the watchdog around one solo denoise phase
    (node/executor.py::_execute). Budget = steps x the slot scheduler's
    step EWMA x factor; with no EWMA evidence the phase runs unwatched
    (cold compiles must never be declared hung). On fire: the device
    health ledger hears a solo hang, and :class:`StepHung` raises once
    the wedged call returns — classified transient, so the PR-2 ladder
    re-runs the job.

    ``key`` identifies the solo program variant (the executor passes
    (model, height, width)): solo executables are per-(model, shape)
    compile-cache entries, so the FIRST watched call per key — which
    may be that program's multi-minute compile — runs under the
    ceiling budget, and only later calls of the same key get the tight
    steps-x-EWMA budget. The warm-key set resets on every cache-flush
    heal rung (the flush drops the solo executables too)."""
    stepper = getattr(slot, "_stepper", None)
    if not watchdog_enabled() or stepper is None:
        yield
        return
    try:
        ewma = float(stepper.step_ewma())
        n_steps = int(steps or 0)
    except (AttributeError, TypeError, ValueError):
        yield
        return
    budget = solo_hang_budget_s(ewma, n_steps)
    if budget is None:
        yield
        return
    epoch = flush_epoch()
    state = getattr(slot, "_guard_solo_warm", None)
    warm_keys = (state[1] if isinstance(state, tuple)
                 and state[0] == epoch else set())
    if key not in warm_keys:
        floor = _env_float(ENV_HANG_FLOOR, 30.0)
        budget = max(floor, _env_float(ENV_HANG_CEIL, 600.0))
    guard = getattr(slot, "_guard", None)

    def on_hang() -> None:
        if guard is not None:
            guard.note_hang(_slot_devices(slot), phase="solo")

    ticket = WATCHDOG.arm(budget, on_hang, tag="solo-denoise")
    fired = False
    try:
        yield
    finally:
        fired = WATCHDOG.disarm(ticket)
    if fired:
        raise StepHung(
            f"solo denoise exceeded its {budget:.1f}s hang budget "
            f"(declared hung; retrying through the ladder)")
    try:
        warm_keys.add(key)
        slot._guard_solo_warm = (epoch, warm_keys)
    except (AttributeError, TypeError):  # exotic slot stubs
        pass


# ---------------------------------------------------------------------------
# output validation
# ---------------------------------------------------------------------------


def screen_images(images: Any, *, context: str = "decode") -> None:
    """Post-decode screen: raise :class:`InvalidOutput` when decoded
    frames are numerically poisoned — non-finite values (float stages)
    or a CONSTANT frame (a NaN trajectory casts to a flat/black frame
    in uint8; a legitimate generation is never exactly constant). Runs
    on the host copy the result path already holds, so it costs one
    pass over pixels and no extra transfer."""
    if not validation_enabled():
        return
    arr = np.asarray(images)
    if arr.size == 0:
        return
    if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
        raise InvalidOutput(
            f"non-finite pixel values after {context}; refusing to "
            f"upload a poisoned image")
    # ndim >= 4 is a (B, H, W, C) batch; anything smaller is ONE image
    # (the OutputProcessor convention) — iterating an (H, W, C) image
    # as H "frames" would flag any legitimate solid border row
    frames = arr if arr.ndim >= 4 else arr[None]
    for i, frame in enumerate(frames):
        flat = np.asarray(frame)
        if flat.size and flat.max() == flat.min():
            raise InvalidOutput(
                f"frame {i} is constant (value {flat.flat[0]!r}) after "
                f"{context} — a poisoned trajectory, not an image")


# ---------------------------------------------------------------------------
# chaos seams
# ---------------------------------------------------------------------------

_CHAOS_LOCK = threading.Lock()
_CHAOS_CONSUMED: set[str] = set()


def consume_chaos(kind: str) -> bool:
    """One-shot chaos gate: the first caller for ``kind`` wins, so a
    scripted wedge/NaN fires in exactly one lane process-wide no matter
    how many lanes reach the trigger step."""
    with _CHAOS_LOCK:
        if kind in _CHAOS_CONSUMED:
            return False
        _CHAOS_CONSUMED.add(kind)
        return True


def reset_chaos() -> None:
    """Re-arm the one-shot chaos seams (tests)."""
    with _CHAOS_LOCK:
        _CHAOS_CONSUMED.clear()


@dataclasses.dataclass(frozen=True)
class LaneChaos:
    """Parsed lane chaos plan. Lanes re-read the env at every dispatch
    (serving/stepper.py) and count trigger steps RELATIVE to the step
    at which the plan first appeared — so a test can warm lanes first,
    then arm a wedge/NaN that fires a deterministic number of steps
    later, on fresh and reused lanes alike."""

    wedge_step: int | None = None
    wedge_s: float = 0.0
    slow_mult: float = 1.0
    nan_step: int | None = None
    nan_row: int = 0

    @classmethod
    def from_env(cls) -> "LaneChaos":
        def pair(name: str) -> tuple[int, float] | None:
            raw = os.environ.get(name, "").strip()
            if not raw or ":" not in raw:
                return None
            a, b = raw.split(":", 1)
            try:
                return int(a), float(b)
            except ValueError:
                return None

        wedge = pair(ENV_CHAOS_WEDGE)
        nan = pair(ENV_CHAOS_NAN)
        return cls(
            wedge_step=None if wedge is None else wedge[0],
            wedge_s=0.0 if wedge is None else wedge[1],
            slow_mult=max(1.0, _env_float(ENV_CHAOS_SLOW, 1.0)),
            nan_step=None if nan is None else nan[0],
            nan_row=0 if nan is None else int(nan[1]),
        )

    @property
    def armed(self) -> bool:
        return (self.wedge_step is not None or self.nan_step is not None
                or self.slow_mult > 1.0)

    def wedge_at(self, step: int) -> float:
        """Seconds to wedge inside lane step ``step`` (0 = no wedge)."""
        if self.wedge_step is None or step != self.wedge_step:
            return 0.0
        return self.wedge_s if consume_chaos("wedge") else 0.0

    def nan_wants(self, step: int) -> int | None:
        """Row the NaN seam WANTS to poison at (or after) lane step
        ``step`` — the lane consumes the one-shot only once the row is
        actually ELIGIBLE (active and mid-trajectory): a seam spent on
        a padding row or a row about to retire would prove nothing."""
        if self.nan_step is None or step < self.nan_step:
            return None
        return self.nan_row

    def slow_extra_s(self, step_s: float) -> float:
        """Extra sleep stretching this step to ~slow_mult x its time."""
        if self.slow_mult <= 1.0:
            return 0.0
        return max(0.0, float(step_s) * (self.slow_mult - 1.0))


# ---------------------------------------------------------------------------
# device health + the healing ladder
# ---------------------------------------------------------------------------

#: heal rung vocabulary (escalation order; ``lane_rebuild`` is counted
#: on every condemnation — it IS the condemnation — the later rungs
#: queue worker-side actions)
HEAL_RUNGS = ("lane_rebuild", "cache_flush", "device_quarantine",
              "restart")

#: hang phases the counter labels by
HANG_PHASES = ("lane", "solo")

#: streak weight per event kind: a hang is stronger evidence of a sick
#: device than one slow step or one poisoned row
EVENT_WEIGHTS = {"hang": 2, "invalid_output": 1, "slow_step": 1}


@dataclasses.dataclass(frozen=True)
class HealAction:
    """One queued ladder action for the worker to apply."""

    rung: str
    device: str
    reason: str


class DeviceGuard:
    """Per-device health ledger + the healing-ladder policy.

    Events (hangs, slow steps, invalid outputs) grow a per-device
    sickness STREAK — weighted, consecutive: any OK event shrinks it —
    and the health gauge derives from the streak
    (``1 - streak / restart_after``, floored at 0). Crossing a rung
    threshold queues exactly one :class:`HealAction` per rung per
    sickness episode; the worker applies them from its poll loop
    (node/worker.py::_apply_heal_rungs) and the episode's rungs re-arm
    once the device recovers to streak 0.

    Thread-safe on an injectable clock; hermetic per worker (metrics
    land on the worker's registry, like the overload controller)."""

    def __init__(self, *, enabled: bool = True,
                 cache_flush_after: int = 3,
                 quarantine_after: int = 5,
                 restart_after: int = 7,
                 clock: Callable[[], float] = time.monotonic,
                 metrics_registry: Any = None) -> None:
        self.enabled = bool(enabled)
        self.cache_flush_after = max(1, int(cache_flush_after))
        self.quarantine_after = max(self.cache_flush_after,
                                    int(quarantine_after))
        self.restart_after = max(self.quarantine_after, int(restart_after))
        self._clock = clock
        self._lock = threading.Lock()
        self._streak: dict[str, int] = {}
        #: rung index (into HEAL_RUNGS) already queued this episode
        self._rung_done: dict[str, int] = {}
        self._actions: collections.deque[HealAction] = collections.deque()
        self.quarantined: set[str] = set()
        self.restart_requested = False
        self.hangs_total = 0
        self.invalid_total = 0
        self.slow_total = 0
        self.condemned_lanes = 0
        reg = metrics_registry
        self._m_hangs = obs_metrics.guard_hangs_counter(reg)
        self._m_condemned = obs_metrics.guard_condemned_counter(reg)
        self._m_invalid = obs_metrics.guard_invalid_counter(reg)
        self._m_health = obs_metrics.guard_device_health_gauge(reg)
        self._m_rungs = obs_metrics.guard_heal_rung_counter(reg)
        self._m_quarantined = obs_metrics.guard_quarantined_gauge(reg)
        # pre-seed every enumerable vocabulary so the families render
        # zeroes from the FIRST scrape (the ISSUE-6 convention)
        for phase in HANG_PHASES:
            self._m_hangs.inc(0, phase=phase)
        for rung in HEAL_RUNGS:
            self._m_rungs.inc(0, rung=rung)
        self._m_condemned.inc(0)
        self._m_quarantined.set(0)

    # ---- event intake ----

    def seed_devices(self, devices: Iterable[str]) -> None:
        """Register the devices this worker serves so their health
        gauges render 1.0 before any event lands."""
        with self._lock:
            for device in devices:
                self._streak.setdefault(str(device), 0)
        self._publish_health()

    def note_hang(self, devices: Iterable[str], phase: str = "lane") -> None:
        with self._lock:
            self.hangs_total += 1
        self._m_hangs.inc(phase=phase if phase in HANG_PHASES else "lane")
        self._note_bad(devices, "hang")

    def note_condemned(self) -> None:
        with self._lock:
            self.condemned_lanes += 1
        self._m_condemned.inc()
        self._m_rungs.inc(rung="lane_rebuild")

    def note_invalid_output(self, devices: Iterable[str],
                            model: str = "") -> None:
        with self._lock:
            self.invalid_total += 1
        self._m_invalid.inc(model=str(model or "unknown"))
        self._note_bad(devices, "invalid_output")

    def note_slow_step(self, devices: Iterable[str]) -> None:
        with self._lock:
            self.slow_total += 1
        self._note_bad(devices, "slow_step")

    def note_ok(self, devices: Iterable[str]) -> None:
        """A healthy step/job on these devices: the sickness streak
        decays (one weight unit per OK), and a device that reaches 0
        re-arms its ladder for the next episode."""
        with self._lock:
            for device in (str(d) for d in devices):
                streak = max(0, self._streak.get(device, 0) - 1)
                self._streak[device] = streak
                if streak == 0:
                    self._rung_done.pop(device, None)
        self._publish_health()

    def _note_bad(self, devices: Iterable[str], kind: str) -> None:
        weight = EVENT_WEIGHTS.get(kind, 1)
        queued: list[HealAction] = []
        with self._lock:
            for device in (str(d) for d in devices):
                streak = self._streak.get(device, 0) + weight
                self._streak[device] = streak
                if not self.enabled:
                    continue
                done = self._rung_done.get(device, 0)
                for rung_idx, (rung, threshold) in enumerate((
                        ("cache_flush", self.cache_flush_after),
                        ("device_quarantine", self.quarantine_after),
                        ("restart", self.restart_after)), start=1):
                    if streak >= threshold and done < rung_idx:
                        # event attribution is SLOT-granular (every
                        # device of a slot hears every event), so all
                        # its chips cross each threshold together:
                        # queue each rung ONCE per call — and
                        # quarantine amputates at most one chip per
                        # process; if sickness continues, the next
                        # rung (restart) is the honest answer, not
                        # shrinking a healthy mesh chip by chip
                        repeat = any(a.rung == rung for a in queued)
                        if rung == "device_quarantine" and (
                                repeat or self.quarantined):
                            done = rung_idx
                            continue
                        if repeat:
                            done = rung_idx
                            continue
                        reason = (f"device {device} sickness streak "
                                  f"{streak} >= {threshold} ({kind})")
                        queued.append(HealAction(rung, device, reason))
                        done = rung_idx
                        if rung == "device_quarantine":
                            self.quarantined.add(device)
                        elif rung == "restart":
                            self.restart_requested = True
                self._rung_done[device] = done
            for action in queued:
                self._actions.append(action)
        for action in queued:
            self._m_rungs.inc(rung=action.rung)
            log.error("guard ladder: %s queued (%s)", action.rung,
                      action.reason)
        self._m_quarantined.set(len(self.quarantined))
        self._publish_health()

    def _publish_health(self) -> None:
        with self._lock:
            scores = {device: max(0.0, 1.0 - streak / self.restart_after)
                      for device, streak in self._streak.items()}
        for device, score in scores.items():
            self._m_health.set(round(score, 4), device=device)

    def health_scores(self) -> dict[str, float]:
        with self._lock:
            return {device: round(
                max(0.0, 1.0 - streak / self.restart_after), 4)
                for device, streak in sorted(self._streak.items())}

    # ---- the worker drains queued actions ----

    def take_actions(self) -> list[HealAction]:
        with self._lock:
            actions = list(self._actions)
            self._actions.clear()
        return actions

    def snapshot(self) -> dict[str, Any]:
        """The /healthz ``guard`` key (node/worker.py)."""
        with self._lock:
            streaks = dict(sorted(self._streak.items()))
            return {
                "enabled": self.enabled,
                "hangs": self.hangs_total,
                "condemned_lanes": self.condemned_lanes,
                "invalid_outputs": self.invalid_total,
                "slow_steps": self.slow_total,
                "streaks": streaks,
                "health": {d: round(max(0.0, 1.0 - s / self.restart_after),
                                    4) for d, s in streaks.items()},
                "quarantined": sorted(self.quarantined),
                "restart_requested": self.restart_requested,
                "rungs": {"cache_flush_after": self.cache_flush_after,
                          "quarantine_after": self.quarantine_after,
                          "restart_after": self.restart_after},
            }
