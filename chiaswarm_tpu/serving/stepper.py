"""Continuous step-level batching: the UNet step is the scheduling quantum.

Burst coalescing (node/executor.py::synchronous_do_work_batch) only merges
jobs that arrive in the SAME poll with identical static params — a job that
arrives one poll later waits behind a full solo program. This module applies
iteration-level admission (Orca-style continuous batching, popularized for
LLM serving by vLLM) to diffusion: one resident batched denoise program per
(model, bucketed-shape, steps-capacity, sampler) **lane** executes ONE step
per call over a fixed lane width of rows; incoming jobs splice into free
row slots at the next step boundary, finished rows retire early and their
VAE decode + host transfer overlap the ongoing UNet steps.

Per-row traced state (latents, carry keys, step index, START index,
sigma/timestep tables, guidance, multistep history, inpaint mask/known
stacks, ControlNet hint embeddings, active mask) makes rows at different
progress — and with different step counts and WORKLOADS — coexist in one
program; the per-row math is a ``vmap`` of the solo sampler step, so
every row walks exactly its solo trajectory (the numerical-equivalence
gate, tests/test_stepper.py). Since ISSUE 7 lanes are the ENGINE, not
the experiment: the default is ON (``CHIASWARM_STEPPER=0`` opts out and
restores the burst/solo routing), and eligibility spans txt2img,
img2img (per-row denoise start indices), inpaint (per-row mask + clean
latents, reprojected by the shared sampler helper) and ControlNet
(bundle-keyed lanes; per-row hint embeddings + conditioning scales).
Admission never compiles: the lane executables (encode / row-init /
control-embed / step / decode, pipelines/diffusion.py ``stepper_*_fn``)
are keyed by buckets alone.

Lane capacity is a CLOSED LOOP (ISSUE 7c): instead of a fixed width,
each lane carries a :class:`LaneWidthController` that follows the
scheduler's arrival-rate EWMA (fed by submissions) plus the worker
poll loop's short-lived row hints, and the lane's occupancy EWMA — the
same signal the
``chiaswarm_stepper_lane_occupancy_ratio`` histogram exports. A lane
is as wide as the smallest lattice bucket that holds the rows it has
evidence for (resident + pending + what a fresh poll hint still
announces) and no wider: it OPENS at its first job's bucket
(``StepScheduler.initial_width`` — a lone one-image job rides a
width-1 lane, ISSUE 27), grows when pending rows cannot fit (or
occupancy stays high while arrivals continue) and shrinks when
occupancy stays low — down to 1 under a lone row — ONLY at step
boundaries, and only onto the pow2 width lattice the compile cache
already buckets by — so a resize reuses (or compiles once, bounded) a
lattice program, and admission itself still never compiles.

Fault containment composes with the PR-2 machinery: a failed lane fails
every resident row's future — the executor falls back to the per-job path
(where the OOM ladder splits and retries), so the chaos zero-loss
invariant (every job -> exactly one envelope or dead-letter) holds; rows
carry their own in-lane deadline; an OOM'd lane additionally halves the
lane width it will rebuild with. ``drain``/``shutdown`` retire lanes
cleanly on worker stop.

Fleet durability (ISSUE 6): when the owning worker attaches a
checkpoint spool to the slot (``slot._checkpoint_spool``,
node/worker.py), each lane snapshots every resident job's per-row state
— latents, carry PRNG keys, multistep history, step index — at step
boundaries, every ``CHIASWARM_STEPPER_CKPT_EVERY`` steps. The worker's
heartbeat pushes the latest snapshot to a lease-aware hive
(node/minihive.py); a job redelivered after this worker dies arrives
with a ``resume`` payload and splices into a lane at step k through the
SAME mid-flight admission path fresh jobs use — restored rows walk the
identical solo trajectory from step k because keys/latents/history are
bit-exact.

Knobs (operator guide: README "Continuous batching" and "Fleet
operations"):

- ``CHIASWARM_STEPPER=0``  opt OUT of lane routing (default on)
- ``CHIASWARM_STEPPER_LANE_WIDTH``  PIN rows per lane (disables the
  adaptive controller; unset = adaptive width over the pow2 lattice)
- ``CHIASWARM_STEPPER_ADAPTIVE=0``  disable adaptive width without
  pinning (lanes stay at their initial width)
- ``CHIASWARM_STEPPER_MIN_WIDTH`` / ``_MAX_WIDTH``  adaptive bounds
  (defaults: 1 and 4x the slot-saturation heuristic, pow2-bucketed)
- ``CHIASWARM_STEPPER_ROW_DEADLINE_S``  per-row in-lane deadline (600)
- ``CHIASWARM_STEPPER_IDLE_S``  idle grace before a lane retires (15)
- ``CHIASWARM_STEPPER_CKPT_EVERY``  steps between lane checkpoints
  (default 8; 0 disables — each snapshot costs one device->host copy
  of the lane state)
- ``CHIASWARM_STEPPER_STEP_DELAY_S``  artificial per-step delay
  (chaos/test seam: stretches lane wall time so fleet faults can land
  deterministically mid-lane; keep 0 in production)

Gray-failure guard (ISSUE 10, serving/guard.py): every step dispatch
runs under the watchdog's hang budget (k x the step EWMA) — a wedged
call condemns the lane from the monitor thread and its rows re-admit
to a freshly built lane, resuming from the last step-boundary
checkpoint; the checkpoint transfer doubles as a per-row finite-check,
so a NaN-poisoned row retires ``invalid_output`` without touching its
peers. ``CHIASWARM_GUARD*`` knobs and the ``CHIASWARM_CHAOS_*`` seams
(scripted wedge / slow-step / NaN) are documented there.
"""

from __future__ import annotations

import base64
import collections
import contextlib
import dataclasses
import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Any

import numpy as np

from chiaswarm_tpu.obs import numerics as _numerics
from chiaswarm_tpu.obs.metrics import (
    REGISTRY,
    STEPPER_UNET_EVAL_MODES,
    arrival_rate_gauge,
    lane_admissions_counter,
    lane_occupancy_histogram,
    lane_resizes_counter,
    resume_step_histogram,
    steps_skipped_counter,
    unet_evals_counter,
    unet_evals_per_image_histogram,
)
from chiaswarm_tpu.obs.trace import span

# swarmguard (ISSUE 10): the in-flight step watchdog, per-row output
# validation, and the chaos seams that prove them deterministically
from chiaswarm_tpu.serving import guard as _guard
from chiaswarm_tpu.serving.guard import InvalidOutput, LaneHung

# the rows/second EWMA the width controllers read is the SAME demand
# primitive the residency manager ranks prefetch candidates with — one
# implementation, shared (ISSUE 8 reuses the ISSUE-7c pattern)
from chiaswarm_tpu.serving.residency import ArrivalEwma as _ArrivalEwma

log = logging.getLogger("chiaswarm.stepper")

# per-step latency distribution under mixed admission — THE signal lane
# width and deadline tuning read (ISSUE 4). Process-global registry: the
# lane drivers are detached threads without a worker handle; /metrics
# serves this registry alongside the worker's own. The timer wraps the
# dispatch INCLUDING the depth-2 window throttle, so in steady state it
# converges on the true device step latency, not the async-submit cost.
_STEP_SECONDS = REGISTRY.histogram(
    "chiaswarm_stepper_step_seconds",
    "lane step wall time (dispatch + pipelined-window backpressure)",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0))
_LANE_ADMIT_SECONDS = REGISTRY.histogram(
    "chiaswarm_stepper_admission_seconds",
    "submit-side admission prep (tokenize + encode + row init)",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0))
# per-lane occupancy ratio at each step (obs/metrics.py ISSUE-5 tie-in):
# distribution over time, where /healthz's lane_occupancy is only the
# lifetime average
_LANE_OCCUPANCY = lane_occupancy_histogram()
# resume telemetry (ISSUE 6): which step redelivered rows splice back in
# at — the fleet-level proof that redelivery resumes instead of
# restarting (obs/metrics.py documents the tuning story)
_RESUME_STEP = resume_step_histogram()
# the lane driver's boundary, part by part (ISSUE 26): everything the
# driver thread does that is not the step dispatch above — each part is
# also a ``lane.<part>`` span (obs/trace.py), so the same name reads on
# the profiler's clock. ``drain`` (the depth-2 window wait) lies INSIDE
# the step timer; ``checkpoint`` took over what
# chiaswarm_stepper_checkpoint_seconds measured (nothing read it).
# Sum and count are what is read (perfbench: lane_host_ms.lat). Parts:
# admit, drain, retire, checkpoint, handoff, idle.
_BOUNDARY_SECONDS = REGISTRY.histogram(
    "chiaswarm_stepper_boundary_seconds",
    "lane driver wall time outside the step dispatch, by part",
    labelnames=("part",),
    buckets=(0.0001, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0))


@contextlib.contextmanager
def _lane_part(part: str):
    """One part of the driver's boundary: a ``lane.<part>`` span on both
    clocks, observed into the boundary histogram."""
    with span("lane." + part) as timed:
        yield
    _BOUNDARY_SECONDS.observe(timed.duration_s, part=part)
# adaptive-width control loop (ISSUE 7c): resize actions, the demand
# EWMA, and per-workload admission breadth — declared in obs/metrics.py
_LANE_RESIZES = lane_resizes_counter()
_ARRIVAL_RATE = arrival_rate_gauge()
_LANE_ADMISSIONS = lane_admissions_counter()
# step-collapse families (ISSUE 12): per-row UNet evals by mode, deep-
# blocks-skipped steps, and the per-image full-eval histogram — shared
# with the solo path (pipelines/diffusion.py increments the same
# process-global families per submitted job)
_UNET_EVALS = unet_evals_counter()
_STEPS_SKIPPED = steps_skipped_counter()
_EVALS_PER_IMAGE = unet_evals_per_image_histogram()

ENV_ENABLE = "CHIASWARM_STEPPER"
ENV_LANE_WIDTH = "CHIASWARM_STEPPER_LANE_WIDTH"
ENV_ADAPTIVE = "CHIASWARM_STEPPER_ADAPTIVE"
ENV_MIN_WIDTH = "CHIASWARM_STEPPER_MIN_WIDTH"
ENV_MAX_WIDTH = "CHIASWARM_STEPPER_MAX_WIDTH"
ENV_ROW_DEADLINE = "CHIASWARM_STEPPER_ROW_DEADLINE_S"
ENV_IDLE_S = "CHIASWARM_STEPPER_IDLE_S"
ENV_SHARD_ROWS = "CHIASWARM_STEPPER_SHARD_ROWS"
ENV_CKPT_EVERY = "CHIASWARM_STEPPER_CKPT_EVERY"
ENV_STEP_DELAY = "CHIASWARM_STEPPER_STEP_DELAY_S"

#: lane workload kinds (the ``workload`` label vocabulary)
WORKLOADS = ("txt2img", "img2img", "inpaint", "controlnet")

#: lane-info key carrying the job's perf_counter stamps inside the lane
#: (process-local: never reaches ``pipeline_config`` or the wire)
LANE_STAMPS_KEY = "_stamps"

# pre-seed every label vocabulary at import so the control-loop families
# render zeroes from the FIRST /metrics scrape (dashboards need the
# zeroes — the ISSUE-6 convention for the lease/resume families)
_ARRIVAL_RATE.set(0.0)
for _direction in ("grow", "shrink"):
    _LANE_RESIZES.inc(0, direction=_direction)
for _workload in WORKLOADS:
    _LANE_ADMISSIONS.inc(0, workload=_workload)
for _mode in STEPPER_UNET_EVAL_MODES:
    _UNET_EVALS.inc(0, mode=_mode)
_STEPS_SKIPPED.inc(0)


# ---- resume-state packing ------------------------------------------------
#
# Checkpoints must survive JSON serialization end to end: spool file ->
# heartbeat body -> hive store -> redelivered job payload. Arrays ride
# as base64 raw bytes + dtype/shape — exact (bit-for-bit, no float
# round-trip through decimal), compact enough for latent-sized state.


def pack_array(arr: Any) -> dict[str, Any]:
    a = np.ascontiguousarray(np.asarray(arr))
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "b64": base64.b64encode(a.tobytes()).decode("ascii")}


def unpack_array(spec: dict[str, Any]) -> np.ndarray:
    a = np.frombuffer(base64.b64decode(spec["b64"]),
                      dtype=np.dtype(str(spec["dtype"])))
    return a.reshape([int(s) for s in spec["shape"]]).copy()


class ResumeReject(RuntimeError):
    """The resume payload does not match this job (wrong shape/steps,
    corrupt arrays): the job restarts from step 0 — losing progress is
    acceptable, resuming onto the WRONG trajectory is not."""


def stepper_enabled() -> bool:
    """Continuous batching is the DEFAULT engine (ISSUE 7): eligible
    diffusion jobs ride lanes unless the operator opts out with
    ``CHIASWARM_STEPPER=0``, which restores the pre-lane burst/solo
    routing end to end (the per-job fallback path is unchanged either
    way)."""
    return os.environ.get(ENV_ENABLE, "").strip().lower() not in (
        "0", "false", "off", "no")


def adaptive_enabled() -> bool:
    """Adaptive lane width is on by default; a pinned
    ``CHIASWARM_STEPPER_LANE_WIDTH`` or ``CHIASWARM_STEPPER_ADAPTIVE=0``
    turns the controller off (lanes then keep their creation width)."""
    if os.environ.get(ENV_LANE_WIDTH, "").strip():
        return False
    return os.environ.get(ENV_ADAPTIVE, "").strip().lower() not in (
        "0", "false", "off", "no")


def shard_rows_enabled() -> bool:
    """Lane rows ride the mesh's data axis only by opt-in
    (``CHIASWARM_STEPPER_SHARD_ROWS=1``; ``Lane._alloc_dev`` says why)."""
    return os.environ.get(ENV_SHARD_ROWS, "").strip().lower() in (
        "1", "true", "on", "yes")


class LaneReject(RuntimeError):
    """The job cannot ride a lane (too many rows, steps beyond the
    capacity lattice, ...) — run it through the ordinary path."""


class LaneDeadline(TimeoutError):
    """A row exceeded its in-lane deadline and was retired unfinished."""


class LaneRetired(RuntimeError):
    """The lane shut down (drain/stop/fault) before the row completed."""


@dataclasses.dataclass(eq=False)  # identity semantics: membership checks
class _RowJob:                    # must never compare device/numpy fields
    """One job's rows plus everything admission needs. Prepared in the
    SUBMITTING thread (tokenize/encode/init dispatch happen there) so the
    driver stays a pure step pump."""

    job_id: Any
    n_rows: int
    steps: int
    guidance: float
    sigmas: np.ndarray          # (steps+1,) this job's ladder
    timesteps: np.ndarray       # (steps,)
    ctx_u: Any                  # (n, L, D) device
    ctx_c: Any
    pooled_u: Any               # (n, P) device or None (non-XL)
    pooled_c: Any
    keys0: Any                  # (n, ...) carry keys after the init split
    x0: Any                     # (n, lh, lw, C) initial latents
    deadline: float             # absolute time.monotonic() cutoff
    future: Future = dataclasses.field(default_factory=Future)
    admitted_at_step: int = -1
    slots: list[int] = dataclasses.field(default_factory=list)
    # splice-wait telemetry (swarmsight, ISSUE 13): submit vs admit on
    # perf_counter, surfaced as ``splice_wait_s`` in the lane info so
    # the flight record's budget attribution can separate "waited
    # behind a full lane" from "was stepping"
    submitted_t: float = dataclasses.field(
        default_factory=time.perf_counter)
    admitted_t: float = 0.0
    # redelivered-job resume (ISSUE 6): rows splice in at step
    # ``resume_step`` with restored latents/keys and the multistep
    # history ``old0`` instead of freshly drawn noise at step 0
    resume_step: int = 0
    old0: Any = None
    # workload row state (ISSUE 7b): img2img rows start partway down the
    # ladder; inpaint rows carry their latent-grid mask + clean source
    # latents; ControlNet rows carry the pre-embedded hint + scale
    workload: str = "txt2img"
    start_step: int = 0
    known0: Any = None          # (n, lh, lw, C) clean init latents
    mask0: Any = None           # (n, lh, lw, 1) latent mask, 1=regenerate
    cond0: Any = None           # (n, lh, lw, C0) pre-embedded hint
    cscale: float = 1.0         # ControlNet conditioning scale
    # DeepCache step-level reuse (ISSUE 12): the canonical per-job
    # schedule plus resume state — cached deep activations (uncond/cond
    # halves), cache validity, and the skipped-steps tally so a resumed
    # row's per-image eval accounting stays whole-trajectory
    reuse_schedule: tuple[int, ...] = ()
    cache_u0: Any = None        # (n, lh, lw, C1) restored deep cache
    cache_c0: Any = None
    cache_ok0: bool = False
    skipped0: int = 0

    @property
    def idx0(self) -> int:
        """Ladder index a freshly admitted row begins at: the recorded
        resume step for redelivered rows, else the workload's start
        index (0 for txt2img/inpaint, strength-derived for img2img)."""
        return self.resume_step if self.resume_step > 0 else self.start_step


class LaneWidthController:
    """Closed-loop lane capacity (ISSUE 7c): width follows demand.

    Two signals, one actuator. Demand is the scheduler's arrival-rate
    EWMA (rows/sec, fed by submissions and the worker's poll hints);
    supply is the lane's occupancy EWMA — the per-step ratio the
    ``chiaswarm_stepper_lane_occupancy_ratio`` histogram exports.
    Decisions land ONLY at step boundaries (the driver calls
    :meth:`decide` between dispatches — a lane mid-step is untouchable
    by construction) and only onto the pow2 width lattice, so the
    program set stays bounded by the compile-cache buckets:

    - **grow under burst**: pending rows that cannot fit the free slots
      resize immediately to the bucket that holds them; sustained
      occupancy >= ``grow_at`` with arrivals still flowing doubles the
      width ahead of the queue.
    - **shrink under trickle**: occupancy <= ``shrink_at`` for
      ``patience`` consecutive boundaries with nothing pending halves
      the width — padding rows are batched UNet FLOPs burned
      (``lane_fill_pct.lat`` in the benchmark).
    - **a share is only read where it is at least one row** (ISSUE
      27): ``shrink_at`` x width is half a row at width 2, so no
      occupancy a resident row can produce ever meets it, and
      ``grow_at`` x width is under one row at width 1, so every
      resident row meets it. There the test is the rows themselves:
      a lane whose resident + waiting rows have fitted the next
      narrower bucket for ``patience`` boundaries halves (2 -> 1 under
      a lone job), and a lane that has never held two rows does not
      double on the bet that arrivals will overlap — only rows it can
      see (pending, hinted) widen it. At every width where both
      shares are a row or more (>= 4 with the shipped gains) the
      decisions are the share rules above, unchanged.
    - bounds are clamped per decision, so an OOM width-limit recorded
      by the scheduler (``note_oom`` halving) is respected even when it
      arrives between boundaries.

    Pure host arithmetic on an injected clock — unit-testable without
    lanes (tests/test_stepper.py::TestLaneWidthController)."""

    def __init__(self, *, min_width: int = 1, max_width: int = 128,
                 alpha: float = 0.25, grow_at: float = 0.75,
                 shrink_at: float = 0.25, patience: int = 6,
                 rate_window_s: float = 10.0) -> None:
        # defaults are the swarmload harness sweep winner (ISSUE 9:
        # node/loadgen.py::sweep_lane_gains, seed "swarmload" — grow
        # earlier at 0.75 occupancy, hold width until 0.25), and
        # tests/test_loadgen.py pins defaults == winner
        # (pre-sweep statics were grow_at=0.875, shrink_at=0.375)
        self.min_width = max(1, int(min_width))
        self.max_width = max(self.min_width, int(max_width))
        self.alpha = float(alpha)
        self.grow_at = float(grow_at)
        self.shrink_at = float(shrink_at)
        self.patience = max(1, int(patience))
        self.rate_window_s = float(rate_window_s)
        self.occ_ewma = 0.0
        # EWMA-driven moves need ``patience`` boundaries of evidence
        # from birth too — only the pending-cannot-fit burst reaction
        # is allowed to act immediately
        self._boundaries_since_resize = 0
        # consecutive boundaries at which every row the lane knows of
        # fitted the next narrower bucket (the shrink test in rows)
        self._boundaries_fitting_narrower = 0

    def decide(self, width: int, occupied: int, pending_rows: int,
               rate: float, *, max_width: int | None = None) -> int:
        """Target width for the NEXT step, given current occupancy,
        rows waiting at the gate, and the arrival-rate EWMA. Returns
        ``width`` unchanged when the loop holds steady."""
        from chiaswarm_tpu.core.compile_cache import bucket_batch

        hi = self.max_width if max_width is None else max(1, min(
            self.max_width, int(max_width)))
        lo = min(self.min_width, hi)
        self.occ_ewma += self.alpha * (occupied / max(1, width)
                                       - self.occ_ewma)
        self._boundaries_since_resize += 1
        target = width
        need = occupied + pending_rows
        if need <= width // 2:
            self._boundaries_fitting_narrower += 1
        else:
            self._boundaries_fitting_narrower = 0
        if need > width:
            # burst reaction: pending rows must not queue behind a full
            # lane when a wider lattice program can hold them now
            target = bucket_batch(min(need, hi))
        elif self._boundaries_since_resize >= self.patience:
            # a share threshold under one row at this width cannot tell
            # a lone row from a full lane (grow) or from an empty one
            # (shrink): there the rows decide, see the class docstring
            if self.shrink_at * width >= 1.0:
                low = self.occ_ewma <= self.shrink_at
            else:
                low = self._boundaries_fitting_narrower >= self.patience
            if (self.grow_at * width >= 1.0
                    and self.occ_ewma >= self.grow_at and rate > 0.0
                    and width * 2 <= hi):
                target = width * 2
            elif (low and pending_rows == 0
                    and occupied <= width // 2 and width > lo):
                target = width // 2
        target = max(lo, min(hi, bucket_batch(max(1, target))))
        target = max(target, bucket_batch(max(1, occupied)))
        if target != width:
            self._boundaries_since_resize = 0
            self._boundaries_fitting_narrower = 0
            # re-seed the EWMA at the post-resize ratio so one resize
            # does not immediately argue for the next
            self.occ_ewma = occupied / max(1, target)
        return target




class Lane:
    """One resident batched denoise loop: a fixed-width row file through
    one compiled step program, driven by a dedicated thread."""

    _ids = iter(range(1, 1 << 62))

    def __init__(self, sched: "StepScheduler", key: tuple, pipe,
                 *, width: int, height: int, width_px: int,
                 steps_cap: int, sampler, control: Any = None,
                 width_bounds: tuple[int, int] | None = None,
                 reuse: bool = False) -> None:
        self._sched = sched
        self.key = key
        self.pipe = pipe
        self.width = int(width)
        self.height = int(height)
        self.width_px = int(width_px)
        self.steps_cap = int(steps_cap)
        self.sampler = sampler
        # ControlNet lanes are keyed by bundle: every row shares the
        # branch params; hint embeddings + scales stay per row
        self.ctrl = control
        # DeepCache lanes (ISSUE 12) compile the reuse branch in and
        # carry per-row deep-feature caches; keyed separately so plain
        # lanes keep the pre-reuse program
        self.reuse = bool(reuse)
        self.lane_id = next(Lane._ids)
        self._cond = threading.Condition()
        self._pending: collections.deque[_RowJob] = collections.deque()
        self._rows: list[_RowJob | None] = [None] * self.width
        self._stop = False
        self._retired = False
        # eviction→retire (ISSUE 9 satellite): the residency ledger
        # evicted this lane's model — retire the moment the row file
        # drains (idle lanes retire on the next driver wakeup) instead
        # of waiting out the idle grace, so HBM actually frees at
        # eviction
        self._retire_asap = False
        self.steps_executed = 0
        # adaptive capacity (ISSUE 7c): decisions land at step
        # boundaries only; bounds come from the scheduler's policy and
        # are re-clamped per decision by the OOM width limits
        self._adaptive = adaptive_enabled()
        lo, hi = width_bounds if width_bounds else (self.width, self.width)
        self._ctl = LaneWidthController(min_width=lo, max_width=hi)
        # host mirrors of the slow-changing per-row inputs (rebuilt on
        # device only when admission/retirement changes them)
        self._h_start = np.zeros(self.width, np.int32)
        self._h_idx = np.zeros(self.width, np.int32)
        self._h_sig = np.ones((self.width, self.steps_cap + 1), np.float32)
        self._h_ts = np.zeros((self.width, self.steps_cap), np.float32)
        self._h_guid = np.ones(self.width, np.float32)
        self._h_active = np.zeros(self.width, bool)
        self._h_mask_on = np.zeros(self.width, bool)
        self._h_cscale = np.ones(self.width, np.float32)
        # DeepCache row state (reuse lanes only; kept allocated either
        # way so the resize remap stays uniform): which ladder steps
        # each row's schedule wants reused, whether its cache is valid
        # (a full step ran since admission), and its skipped tally
        self._h_reuse = np.zeros((self.width, self.steps_cap), bool)
        self._h_cache_ok = np.zeros(self.width, bool)
        self._h_skipped = np.zeros(self.width, np.int64)
        self._dev = None  # device state dict, allocated at first admission
        self._mesh = None
        self._deferred_counts: list[dict] = []
        self._window: collections.deque = collections.deque()
        # step-boundary resume snapshots (ISSUE 6): only when the owning
        # worker attached its checkpoint spool to the slot
        self._spool = getattr(getattr(sched, "slot", None),
                              "_checkpoint_spool", None)
        self._ckpt_every = int(
            os.environ.get(ENV_CKPT_EVERY, "8") or 8)
        self._step_delay = float(
            os.environ.get(ENV_STEP_DELAY, "0") or 0)
        # swarmguard (ISSUE 10): the watchdog condemns a wedged lane
        # from the MONITOR thread; resume state for the re-admission
        # comes from this in-memory twin of the spool checkpoint (kept
        # even without a spool — condemnation must not depend on the
        # fleet heartbeat being on), and the chaos plan scripts
        # wedge/slow/NaN faults deterministically
        self._condemned = False
        self._ckpt_mem: dict[int, dict[str, Any]] = {}
        # chaos plan is re-read per dispatch; triggers count steps
        # relative to when the CURRENT plan first appeared on THIS
        # lane (a changed plan re-bases, so sequentially-armed seams
        # each get their own step window)
        self._chaos_base: int | None = None
        self._chaos_seen: _guard.LaneChaos | None = None
        # widths whose step program has completed a dispatch in THIS
        # lane: a dispatch at a new width (fresh lane, resize) may
        # COMPILE, so it runs under the watchdog's ceiling budget, not
        # the steady-state EWMA budget; a cache-flush heal rung bumps
        # the epoch and re-colds every lane (serving/guard.py)
        self._warm_widths: set[int] = set()
        self._flush_epoch = _guard.flush_epoch()
        # retired rows whose async decode is still in flight: the future
        # resolves only once the images are RESIDENT (same cross-thread
        # hazard as admission — the consumer must never read an array
        # another thread is still computing)
        self._handoff: collections.deque = collections.deque()
        self._thread = threading.Thread(
            target=self._drive, name=f"stepper-lane-{self.lane_id}",
            daemon=True)
        self._thread.start()

    # ---- submission side ----

    def try_enqueue(self, job: _RowJob) -> bool:
        with self._cond:
            if self._stop or self._retired:
                return False
            self._pending.append(job)
            self._cond.notify_all()
            return True

    def busy(self) -> bool:
        with self._cond:
            return (bool(self._pending) or bool(self._handoff)
                    or any(r is not None for r in self._rows))

    def occupancy(self) -> tuple[int, int]:
        with self._cond:
            return sum(r is not None for r in self._rows), self.width

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()

    def request_retire(self) -> None:
        """Retire as soon as the row file drains (resident rows finish,
        pending rows admitted and finished) — the eviction hook. Unlike
        :meth:`stop` this never fails resident rows: their params are
        still live on device until they release them."""
        with self._cond:
            self._retire_asap = True
            self._cond.notify_all()

    def join(self, timeout: float | None = None) -> None:
        self._thread.join(timeout)

    def condemn(self, reason: str) -> None:
        """Declare this lane HUNG (swarmguard watchdog, ISSUE 10).

        Runs in the watchdog MONITOR thread while the driver is blocked
        inside the wedged dispatch, so it must never touch the device:
        it retires the lane (submitters open a fresh one), clears the
        row file, and fails every job's future with :class:`LaneHung`
        carrying the last in-memory step-boundary checkpoint — the
        executor re-admits those rows to a freshly built lane resuming
        at step k (node/executor.py::_stepper_collect). The wedged
        driver thread notices on return and exits without touching the
        row file it no longer owns."""
        with self._cond:
            if self._retired or self._condemned:
                return
            self._condemned = True
            # retire BEFORE failing over, like _fail_all: a racing
            # submit must see a dead lane and open a fresh one
            self._retired = True
            jobs = {id(j): j for j in self._rows if j is not None}
            pending = [j for j in self._pending]
            self._pending.clear()
            for s in range(self.width):
                self._rows[s] = None
            self._h_active[:] = False
            resumes = {jid: self._ckpt_mem.get(jid) for jid in jobs}
            handoff = list(self._handoff)
            self._handoff.clear()
            self._cond.notify_all()
        # outside the lane lock: _lane_done/_count take sched._lock,
        # which submitters hold while waiting on this lane's cond —
        # nesting them here would invert the order and deadlock
        self._sched._lane_done(self)
        rows_hung = 0
        for jid, job in jobs.items():
            rows_hung += job.n_rows
            if not job.future.done():
                job.future.set_exception(LaneHung(
                    f"lane {self.lane_id} condemned: {reason}",
                    resume=resumes.get(jid)))
        for job in pending:
            rows_hung += job.n_rows
            if not job.future.done():
                job.future.set_exception(LaneHung(
                    f"lane {self.lane_id} condemned with the job still "
                    f"pending: {reason}"))
        for job, _pending_imgs, _info in handoff:
            # the retired rows' decode was dispatched onto the wedged
            # device — waiting on it HERE would wedge the watchdog too;
            # the job re-runs instead (chip time lost, rows never are)
            if not job.future.done():
                job.future.set_exception(LaneHung(
                    f"lane {self.lane_id} condemned with the decode "
                    f"in flight: {reason}"))
        self._sched._count(lanes_condemned=1, rows_hung=rows_hung)
        device_guard = getattr(getattr(self._sched, "slot", None),
                               "_guard", None)
        if device_guard is not None:
            device_guard.note_hang(
                _guard._slot_devices(self._sched.slot), phase="lane")
            device_guard.note_condemned()
        log.error("lane %d CONDEMNED (%s): %d row(s) failed over with "
                  "resume state for a fresh lane", self.lane_id, reason,
                  rows_hung)

    # ---- driver ----

    def _drive(self) -> None:
        idle_s = float(os.environ.get(ENV_IDLE_S, "15") or 15)
        idle_since: float | None = None
        try:
            while True:
                # scheduler-side control signals, read OUTSIDE the lane
                # lock (sched._lock nests inside submitters holding it
                # while they wait on this lane's cond — taking it under
                # self._cond would invert the order and deadlock).
                # Read anew on every pass, a wake from the idle wait
                # included: a poll hint read before the wait announces
                # the very job whose enqueue ends it, and counting it
                # beside that job would widen the lane for nothing
                width_limit = self._sched.width_limit_for(self.key)
                rate, hint_rows = self._sched.demand_signal()
                admit_cap = self._sched.admission_cap()
                with self._cond:
                    if self._stop:
                        raise LaneRetired("lane stopped")
                    with _lane_part("admit"):
                        self._resize_locked(width_limit, rate, hint_rows)
                        self._admit_locked(admit_cap)
                    if not self._h_active.any():
                        if self._retire_asap and not self._pending:
                            # eviction retire: the model left the HBM
                            # ledger and the row file is drained — free
                            # the device state NOW, not after the idle
                            # grace (handoffs were flushed blocking
                            # before the loop came back around)
                            self._retired = True
                            self._deferred_counts.append(
                                dict(lanes_evict_retired=1))
                            return
                        now = time.monotonic()
                        if idle_since is None:
                            idle_since = now
                        elif now - idle_since >= idle_s:
                            if self._pending:
                                # a job the lane can never admit (e.g.
                                # wider than a width-limited lane) must
                                # bounce, not leak an unresolved future
                                raise LaneRetired(
                                    "lane retired with unadmittable "
                                    "pending rows")
                            self._retired = True
                            return
                        # woken by try_enqueue/stop notify; the timeout
                        # only bounds the idle grace itself
                        with _lane_part("idle"):
                            self._cond.wait(timeout=max(
                                0.05, idle_s - (now - idle_since)))
                        continue
                    idle_since = None
                self._flush_counts()
                self._sched._maybe_fault(self)
                with span("lane.step"):
                    self._dispatch_step()
                with _lane_part("retire"):
                    self._retire_rows()
                with _lane_part("checkpoint"):
                    self._maybe_checkpoint()
                with _lane_part("handoff"):
                    self._flush_handoff(block=not self._h_active.any())
        except BaseException as exc:  # noqa: BLE001 — containment seam
            self._fail_all(exc)
        finally:
            with self._cond:
                self._retired = True
            self._flush_counts()
            self._sched._lane_done(self)

    def _flush_counts(self) -> None:
        while self._deferred_counts:
            self._sched._count(**self._deferred_counts.pop(0))

    def _alloc_dev(self, job: _RowJob) -> None:
        import jax.numpy as jnp

        from chiaswarm_tpu.pipelines.diffusion import _params_mesh

        # data parallelism: when the params live on a dp x tp mesh, lane
        # rows ride the 'data' axis — same GSPMD seeding the solo path
        # uses for its token inputs (pipelines/diffusion.py submit). A
        # solo job on a dp slot wastes (dp-1)/dp of the chips; a full
        # lane keeps every data row busy. OPT-IN for now: on the old
        # jax build the row-sharded step program diverged numerically
        # from its unsharded twin (ROADMAP S6 — clean on 0.9.0, not yet
        # re-gated). With the opt-in OFF nothing pins the row arrays:
        # on the chip (dp2 x tp2, PR 21) GSPMD hands the step outputs
        # back sharded P('data') while the program was compiled for
        # replicated rows — jit answers with a silent recompile of the
        # whole step program, an AOT executable with a sharding error.
        self._mesh = None
        if shard_rows_enabled():
            mesh = _params_mesh(self.pipe.c.params)
            if mesh is not None and self.width % mesh.shape["data"] == 0:
                self._mesh = mesh
        lh, lw = self.pipe._latent_hw(self.height, self.width_px)
        ch = self.pipe.c.family.vae.latent_channels
        zero_row = jnp.zeros((self.width, lh, lw, ch), jnp.float32)
        keys = jnp.stack([job.keys0[0]] * self.width)
        placeholder = jnp.zeros((1,), jnp.float32)
        self._dev = {
            "x": zero_row,
            "keys": keys,
            "idx": jnp.zeros(self.width, jnp.int32),
            "old": zero_row,
            "ctx_u": jnp.zeros((self.width,) + job.ctx_u.shape[1:],
                               job.ctx_u.dtype),
            "ctx_c": jnp.zeros((self.width,) + job.ctx_c.shape[1:],
                               job.ctx_c.dtype),
            "pooled_u": (placeholder if job.pooled_u is None else
                         jnp.zeros((self.width,) + job.pooled_u.shape[1:],
                                   job.pooled_u.dtype)),
            "pooled_c": (placeholder if job.pooled_c is None else
                         jnp.zeros((self.width,) + job.pooled_c.shape[1:],
                                   job.pooled_c.dtype)),
            # image-mode row state (ISSUE 7b): clean source latents +
            # latent mask for inpaint rows; mask=1 everywhere keeps
            # non-inpaint rows untouched if the selection ever engages
            "known": zero_row,
            "mask": jnp.ones((self.width, lh, lw, 1), jnp.float32),
            # pre-embedded ControlNet hint rows (control lanes only; a
            # placeholder rides through the no-control step signature)
            "cond": (placeholder if job.cond0 is None else
                     jnp.zeros((self.width,) + job.cond0.shape[1:],
                               job.cond0.dtype)),
        }
        if self.reuse:
            # per-row cached deep activations (uncond/cond halves) —
            # the DeepCache carry the step program refreshes on full
            # steps and replays on reuse steps
            c1 = self.pipe.c.family.unet.block_out_channels[1]
            cache_row = jnp.zeros((self.width, lh, lw, c1),
                                  self.pipe.c.unet.dtype)
            self._dev["cache_u"] = cache_row
            self._dev["cache_c"] = cache_row
        self._sync_tables()

    def _place_rows(self) -> None:
        """Pin every lane-width array onto the mesh's data axis (no-op on
        single-chip slots). Re-applied after admission scatters, whose
        outputs may lose the row sharding."""
        if self._mesh is None:
            return
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        for key, arr in self._dev.items():
            if getattr(arr, "ndim", 0) < 1 or arr.shape[0] != self.width:
                continue  # non-XL pooled placeholders
            spec = P(*(("data",) + (None,) * (arr.ndim - 1)))
            self._dev[key] = jax.device_put(
                arr, NamedSharding(self._mesh, spec))

    def _sync_tables(self) -> None:
        """Rebuild the device copies of the host-mirrored per-row inputs.

        MUST transfer COPIES: jax dispatch is async, and handing it a
        live numpy buffer that admission/retirement later mutates in
        place lets the device read the FUTURE value — the step then e.g.
        sees a row as inactive and silently skips it (observed: a
        one-step job decoding its un-stepped init latents)."""
        import jax.numpy as jnp

        dev = self._dev
        dev["start"] = jnp.asarray(self._h_start.copy())
        dev["sig"] = jnp.asarray(self._h_sig.copy())
        dev["ts"] = jnp.asarray(self._h_ts.copy())
        dev["guid"] = jnp.asarray(self._h_guid.copy())
        dev["active"] = jnp.asarray(self._h_active.copy())
        dev["mask_on"] = jnp.asarray(self._h_mask_on.copy())
        dev["cscale"] = jnp.asarray(self._h_cscale.copy())

    def _admit_locked(self, cap: int | None = None) -> None:
        """Splice pending jobs into free row slots — the step boundary is
        wherever the driver is between dispatches. ``cap`` is the
        brownout rung (node/overload.py via the scheduler): at most that
        many rows splice in per boundary, so resident rows finish ahead
        of fresh admissions under sustained overload. The first pending
        job always admits when slots allow — the cap throttles breadth,
        it must never wedge a job wider than itself."""
        import jax.numpy as jnp

        admitted_rows = 0
        free = [s for s in range(self.width) if self._rows[s] is None]
        while self._pending and self._pending[0].n_rows <= len(free):
            if (cap is not None and admitted_rows > 0
                    and admitted_rows + self._pending[0].n_rows > cap):
                break
            job = self._pending.popleft()
            if job.future.cancelled():
                continue
            # cross-thread handoff sync: the job's arrays were dispatched
            # from the SUBMITTING thread (encode/init overlap earlier lane
            # steps); admit the row only once they are resident. Usually a
            # no-op by now — and this container's jax build corrupts
            # results when a program consumes another thread's still-
            # compiling outputs, so the barrier is correctness, not style.
            for arr in (job.x0, job.keys0, job.ctx_u, job.ctx_c,
                        job.pooled_u, job.pooled_c, job.old0,
                        job.known0, job.mask0, job.cond0,
                        job.cache_u0, job.cache_c0):
                if arr is not None:
                    arr.block_until_ready()
            slots, free = free[:job.n_rows], free[job.n_rows:]
            admitted_rows += job.n_rows
            if self._dev is None:
                self._alloc_dev(job)
            mid_flight = bool(self._h_active.any())
            sel = np.asarray(slots)
            dev = self._dev
            dev["x"] = dev["x"].at[sel].set(job.x0)
            dev["keys"] = dev["keys"].at[sel].set(job.keys0)
            # a resumed row restores its multistep history and rejoins
            # at step k; a fresh row starts clean at its workload's
            # start index — both through the one admission path (the
            # step program never knows the difference)
            dev["old"] = dev["old"].at[sel].set(
                jnp.zeros_like(job.x0) if job.old0 is None else job.old0)
            dev["idx"] = dev["idx"].at[sel].set(job.idx0)
            dev["ctx_u"] = dev["ctx_u"].at[sel].set(job.ctx_u)
            dev["ctx_c"] = dev["ctx_c"].at[sel].set(job.ctx_c)
            if job.pooled_u is not None:
                dev["pooled_u"] = dev["pooled_u"].at[sel].set(job.pooled_u)
                dev["pooled_c"] = dev["pooled_c"].at[sel].set(job.pooled_c)
            dev["known"] = dev["known"].at[sel].set(
                jnp.zeros_like(job.x0) if job.known0 is None
                else job.known0)
            dev["mask"] = dev["mask"].at[sel].set(
                jnp.ones_like(dev["mask"][sel]) if job.mask0 is None
                else job.mask0)
            if job.cond0 is not None:
                dev["cond"] = dev["cond"].at[sel].set(job.cond0)
            if self.reuse:
                # a fresh row starts cache-invalid (its first step runs
                # the full network); a resumed row restores its cache +
                # validity + skipped tally exactly as checkpointed
                dev["cache_u"] = dev["cache_u"].at[sel].set(
                    0.0 if job.cache_u0 is None else job.cache_u0)
                dev["cache_c"] = dev["cache_c"].at[sel].set(
                    0.0 if job.cache_c0 is None else job.cache_c0)
                self._h_reuse[sel, :] = False
                for step_j in job.reuse_schedule:
                    if 0 <= int(step_j) < self.steps_cap:
                        self._h_reuse[sel, int(step_j)] = True
                self._h_cache_ok[sel] = bool(job.cache_ok0)
                self._h_skipped[sel] = int(job.skipped0)
            self._h_idx[sel] = job.idx0
            self._h_start[sel] = job.start_step
            self._h_mask_on[sel] = job.mask0 is not None
            self._h_cscale[sel] = job.cscale
            self._h_sig[sel, :] = 0.0
            self._h_sig[sel, : job.steps + 1] = job.sigmas
            self._h_ts[sel, :] = 0.0
            self._h_ts[sel, : job.steps] = job.timesteps
            self._h_guid[sel] = job.guidance
            self._h_active[sel] = True
            self._sync_tables()
            self._place_rows()
            for s in slots:
                self._rows[s] = job
            job.slots = slots
            job.admitted_at_step = self.steps_executed
            job.admitted_t = time.perf_counter()
            # workload-labeled admission breadth (metric-local lock
            # only — safe under self._cond)
            _LANE_ADMISSIONS.inc(job.n_rows, workload=job.workload)
            # deferred: _admit_locked runs under self._cond while
            # submitters hold sched._lock and wait on self._cond —
            # taking sched._lock (inside _count) HERE would deadlock
            self._deferred_counts.append(dict(
                rows_admitted=job.n_rows,
                rows_admitted_midflight=(job.n_rows if mid_flight
                                         else 0),
                rows_resumed=(job.n_rows if job.resume_step > 0 else 0),
                **{f"rows_admitted_{job.workload}": job.n_rows}))
            if job.resume_step > 0:
                _RESUME_STEP.observe(job.resume_step)
                log.info("job %s resumed at step %d/%d (%d row(s))",
                         job.job_id, job.resume_step, job.steps,
                         job.n_rows)

    def _resize_locked(self, width_limit: int | None, rate: float,
                       hint_rows: int) -> None:
        """Adaptive capacity, applied ONLY here — between dispatches, so
        a step in flight never sees its row file change under it. Runs
        under ``self._cond`` (mutates ``_rows``/host mirrors submitters
        read); the scheduler-side signals were prefetched lock-free by
        the driver. Sharded-row lanes skip (their width must divide the
        mesh data axis; ROADMAP item 2)."""
        if not self._adaptive or self._mesh is not None:
            return
        occupied = sum(r is not None for r in self._rows)
        pending = sum(j.n_rows for j in self._pending
                      if not j.future.cancelled())
        target = self._ctl.decide(self.width, occupied,
                                  pending + max(0, hint_rows), rate,
                                  max_width=width_limit)
        if target == self.width:
            return
        self._apply_resize_locked(target)

    def _apply_resize_locked(self, new_width: int) -> None:
        """Rebuild the row file at ``new_width``: resident rows compact
        onto the first slots (their device state gathered across), host
        mirrors re-seed, and the next dispatch fetches the lattice
        program for the new batch — a cache hit after the first resize
        to any given width."""
        import jax.numpy as jnp

        old_width, self.width = self.width, int(new_width)
        occupied = [(s, self._rows[s]) for s in range(old_width)
                    if self._rows[s] is not None]
        grow = self.width > old_width
        log.info("lane %d %s %d -> %d rows (%d resident)", self.lane_id,
                 "grows" if grow else "shrinks", old_width, self.width,
                 len(occupied))
        _LANE_RESIZES.inc(direction="grow" if grow else "shrink")
        self._deferred_counts.append(dict(lane_resizes=1))
        old_h = (self._h_start, self._h_idx, self._h_sig, self._h_ts,
                 self._h_guid, self._h_active, self._h_mask_on,
                 self._h_cscale, self._h_reuse, self._h_cache_ok,
                 self._h_skipped)
        self._h_start = np.zeros(self.width, np.int32)
        self._h_idx = np.zeros(self.width, np.int32)
        self._h_sig = np.ones((self.width, self.steps_cap + 1), np.float32)
        self._h_ts = np.zeros((self.width, self.steps_cap), np.float32)
        self._h_guid = np.ones(self.width, np.float32)
        self._h_active = np.zeros(self.width, bool)
        self._h_mask_on = np.zeros(self.width, bool)
        self._h_cscale = np.ones(self.width, np.float32)
        self._h_reuse = np.zeros((self.width, self.steps_cap), bool)
        self._h_cache_ok = np.zeros(self.width, bool)
        self._h_skipped = np.zeros(self.width, np.int64)
        new_mirrors = (self._h_start, self._h_idx, self._h_sig, self._h_ts,
                       self._h_guid, self._h_active, self._h_mask_on,
                       self._h_cscale, self._h_reuse, self._h_cache_ok,
                       self._h_skipped)
        for new_s, (old_s, _) in enumerate(occupied):
            for old_m, new_m in zip(old_h, new_mirrors):
                new_m[new_s] = old_m[old_s]
        self._rows = [None] * self.width
        for new_s, (_, job) in enumerate(occupied):
            self._rows[new_s] = job
        for job in {id(j): j for _, j in occupied}.values():
            job.slots = [s for s, (_, j) in enumerate(occupied) if j is job]
        if self._dev is not None:
            sel = jnp.asarray([old_s for old_s, _ in occupied]
                              or [0])[: len(occupied) or None]

            def remap(name, arr):
                # non-XL pooled / no-control placeholders are exactly
                # the 1-D (1,) arrays under these keys: pass them
                # through BY NAME — shape alone misreads them as row
                # state when old_width == 1, and padding a placeholder
                # would change a traced input shape (a recompile no
                # fresh lane ever pays)
                if name in ("pooled_u", "pooled_c", "cond") and \
                        getattr(arr, "ndim", 0) == 1:
                    return arr
                if occupied:
                    taken = jnp.take(arr, sel, axis=0)
                else:
                    taken = arr[:0]
                pad_n = self.width - int(taken.shape[0])
                if pad_n <= 0:
                    return taken
                pad = jnp.zeros((pad_n,) + tuple(arr.shape[1:]), arr.dtype)
                return jnp.concatenate([taken, pad], axis=0)

            self._dev = {k: remap(k, v) for k, v in self._dev.items()}
            self._sync_tables()
            self._place_rows()

    def _dispatch_step(self) -> None:
        dev = self._dev
        fn = self.pipe.stepper_step_fn(
            batch=self.width, height=self.height, width=self.width_px,
            steps_cap=self.steps_cap, sampler=self.sampler,
            has_control=self.ctrl is not None, reuse=self.reuse)
        import jax.numpy as jnp

        # DeepCache step decision (ISSUE 12), made HOST-side from the
        # mirrors this driver owns: skip the deep blocks only when EVERY
        # active row's schedule wants reuse at its current step AND
        # holds a valid cache. The flag rides as a traced scalar, so
        # the decision never recompiles; misaligned lane mates degrade
        # the step to a full eval — more compute, never wrong math.
        reuse_now = False
        if self.reuse and self._h_active.any():
            step_of = np.minimum(self._h_idx, self.steps_cap - 1)
            wants = self._h_reuse[np.arange(self.width), step_of]
            reuse_now = bool(np.all(
                ~self._h_active | (wants & self._h_cache_ok)))

        ctrl_params = (self.ctrl.params if self.ctrl is not None
                       else {"zero": jnp.zeros((1,), jnp.float32)})
        this_step = self.steps_executed + 1
        # chaos plan (swarmguard seams): env re-read each dispatch, and
        # trigger steps count from the dispatch that first SAW the plan
        # — deterministic on fresh and warm (reused) lanes alike
        chaos = _guard.LaneChaos.from_env()
        if not chaos.armed:
            self._chaos_base = self._chaos_seen = None
        elif chaos != self._chaos_seen:
            self._chaos_base = self.steps_executed
            self._chaos_seen = chaos
        chaos_step = (this_step - self._chaos_base
                      if self._chaos_base is not None else 0)
        # swarmguard (ISSUE 10): arm the hang watchdog around the whole
        # dispatch INCLUDING the depth-2 window drain — that drain is
        # where a wedged device actually blocks this thread. Budget is
        # k x the scheduler's step EWMA, EXCEPT when this dispatch may
        # compile — the lane's first dispatch at this width, or the
        # first after a cache-flush heal rung — which runs under the
        # generous ceiling instead: a compile is not a gray failure,
        # and condemning one would feed the very ladder that caused it.
        # If the watchdog fires while we are away, the lane was
        # condemned from the MONITOR thread (rows already failed over
        # with their resume state) — this thread just exits without
        # touching the dead row file.
        epoch = _guard.flush_epoch()
        if epoch != self._flush_epoch:
            self._flush_epoch = epoch
            self._warm_widths.clear()
        budget = self._sched.hang_budget()
        if budget is not None and self.width not in self._warm_widths:
            budget = _guard.hang_budget_s(0.0)  # the cold ceiling
        ticket = None
        if budget is not None:
            ticket = _guard.WATCHDOG.arm(
                budget, lambda: self.condemn(
                    f"step {this_step} exceeded its {budget:.1f}s hang "
                    f"budget"),
                tag=f"lane-{self.lane_id}-step-{this_step}")
        t0 = time.perf_counter()
        fired = False
        try:
            base_args = (
                self.pipe.c.params,
                dev["ctx_u"], dev["ctx_c"], dev["pooled_u"],
                dev["pooled_c"],
                dev["x"], dev["keys"], dev["idx"],
                dev["start"], dev["sig"], dev["ts"], dev["guid"],
                dev["old"], dev["active"],
                dev["known"], dev["mask"], dev["mask_on"],
                ctrl_params, dev["cond"], dev["cscale"],
            )
            if self.reuse:
                (dev["x"], dev["keys"], dev["idx"], dev["old"],
                 dev["cache_u"], dev["cache_c"]) = fn(
                    *base_args, dev["cache_u"], dev["cache_c"],
                    jnp.asarray(reuse_now))
            else:
                dev["x"], dev["keys"], dev["idx"], dev["old"] = fn(
                    *base_args)
            wedge_s = chaos.wedge_at(chaos_step)
            if wedge_s > 0:  # scripted wedged-compiled-call stand-in
                log.warning("chaos: wedging lane %d step %d for %.1fs",
                            self.lane_id, this_step, wedge_s)
                time.sleep(wedge_s)
            # throttle: keep at most two dispatched steps in flight
            # (the depth-2 philosophy of core/chip_pool.py) so the
            # async queue cannot run away from the device — and
            # execution errors surface here, inside the containment
            # try of the driver loop
            self._window.append(dev["x"])
            if len(self._window) > 2:
                # the host legitimately waiting on the chip
                with _lane_part("drain"):
                    self._window.popleft().block_until_ready()
        finally:
            if ticket is not None:
                fired = _guard.WATCHDOG.disarm(ticket)
        if fired:
            # the watchdog declared this step hung. condemn() usually
            # already ran in the monitor thread — but the monitor marks
            # ``fired`` BEFORE invoking the callback, so a dispatch
            # returning in that window could reach _fail_all first and
            # strand the rows with a resume-less LaneRetired. Condemn
            # from HERE too (idempotent): whichever thread wins, every
            # job fails over as LaneHung with its checkpoint, and the
            # hang reaches the device-health ledger exactly once.
            self.condemn(
                f"step {this_step} exceeded its hang budget")
            raise LaneRetired(
                f"lane {self.lane_id} condemned by the hang watchdog "
                f"at step {this_step}")
        self._warm_widths.add(self.width)  # this width's program ran
        nan_row = chaos.nan_wants(chaos_step)
        if nan_row is not None:  # scripted trajectory poisoning —
            # consume the one-shot only when the target row is ACTIVE
            # with at least one more step boundary before retirement,
            # so the poison is deterministically caught by the
            # checkpoint-boundary finite-check (a seam spent on a
            # padding row or a retiring row was the fleet-gate flake)
            row = min(max(0, int(nan_row)), self.width - 1)
            victim = self._rows[row]
            if (victim is not None and self._h_active[row]
                    and int(self._h_idx[row]) + 1 < victim.steps
                    and _guard.consume_chaos("nan")):
                log.warning("chaos: poisoning lane %d row %d with NaN "
                            "after step %d", self.lane_id, row,
                            this_step)
                dev["x"] = dev["x"].at[row].set(jnp.nan)
        active = int(self._h_active.sum())
        if self.reuse and reuse_now:
            # this dispatch replayed the deep cache: every active row
            # skipped its deep blocks — the step-collapse tally the
            # per-image eval accounting and /metrics families read
            self._h_skipped[self._h_active] += 1
            _UNET_EVALS.inc(active, mode="reuse")
            _STEPS_SKIPPED.inc(active)
            self._sched._count(steps_reused=1, row_steps_reused=active)
        else:
            if self.reuse:
                # a full step refreshed every active row's cache
                self._h_cache_ok[self._h_active] = True
            _UNET_EVALS.inc(active, mode="full")
        self._h_idx[self._h_active] += 1
        self.steps_executed += 1
        self._sched._count(steps_executed=1, row_steps_active=active,
                           row_steps_padded=self.width - active)
        _LANE_OCCUPANCY.observe(active / self.width, width=str(self.width))
        if self._step_delay > 0:  # chaos seam: stretch lane wall time
            time.sleep(self._step_delay)
        step_s = time.perf_counter() - t0
        slow_extra = chaos.slow_extra_s(step_s)
        if slow_extra > 0:  # chaos: the sick-but-alive device
            time.sleep(slow_extra)
            step_s += slow_extra
        _STEP_SECONDS.observe(step_s)
        # the overload estimator's lane-path signal (node/overload.py):
        # job steps x this EWMA floors the predicted service time —
        # and the guard's slow-step health signal AND hang budget read
        # the same EWMA. The lane's FIRST dispatch compiles (seconds to
        # minutes); feeding it would poison the EWMA and inflate the
        # watchdog's hang budget k-fold for many steps — a real wedge
        # would then sail under the budget. Skip it: the watchdog
        # already covers the cold window with the ceiling budget.
        ewma_before = self._sched.step_ewma()
        if self.steps_executed > 1:
            self._sched.note_step_seconds(step_s)
        device_guard = getattr(getattr(self._sched, "slot", None),
                               "_guard", None)
        if device_guard is not None:
            devices = _guard._slot_devices(self._sched.slot)
            if ewma_before > 0 and step_s > _guard.slow_factor() * \
                    ewma_before:
                self._sched._count(steps_slow=1)
                device_guard.note_slow_step(devices)
            else:
                device_guard.note_ok(devices)

    def _retire_rows(self) -> None:
        """Retire finished rows (decode dispatched async — it overlaps the
        next steps) and expire rows past their deadline."""
        from chiaswarm_tpu.core.compile_cache import bucket_batch
        from chiaswarm_tpu.pipelines.diffusion import PendingImages

        import jax.numpy as jnp

        now = time.monotonic()
        done: list[_RowJob] = []
        expired: list[_RowJob] = []
        for s, job in enumerate(self._rows):
            if job is None or not self._h_active[s]:
                continue
            if self._h_idx[s] >= job.steps and job not in done:
                done.append(job)
            elif now > job.deadline and job not in expired \
                    and self._h_idx[s] < job.steps:
                expired.append(job)
        changed = False
        for job in done:
            sel = np.asarray(job.slots)
            rows_x = jnp.take(self._dev["x"], jnp.asarray(sel), axis=0)
            bucket = bucket_batch(job.n_rows)
            if job.n_rows < bucket:
                rows_x = jnp.concatenate(
                    [rows_x, jnp.repeat(rows_x[-1:],
                                        bucket - job.n_rows, axis=0)])
            decode = self.pipe.stepper_decode_fn(
                batch=bucket, height=self.height, width=self.width_px)
            with span("lane.decode"):
                images = decode(self.pipe.c.params, rows_x)
            pending = PendingImages(
                device_images=images,
                compiled_hw=(self.height, self.width_px),
                requested_hw=(self.height, self.width_px),
                requested_batch=job.n_rows)
            info = {
                "lane": self.lane_id,
                "lane_width": self.width,
                "admitted_at_step": job.admitted_at_step,
                "steps_executed": self.steps_executed,
                # the fleet-invariant proof point: >0 means this job was
                # redelivered and resumed mid-trajectory, not restarted
                "resume_step": job.resume_step,
                # time the rows waited for a free slot before their
                # first step (flight-record lane_wait attribution)
                "splice_wait_s": round(
                    max(0.0, job.admitted_t - job.submitted_t), 6)
                if job.admitted_t else 0.0,
                # the job's time inside the lane, on this process's
                # perf_counter: stamps the driver already passes (no
                # new synchronisation). ``resolved`` lands when the
                # future does; workloads.stepper_finish pops the key
                # and turns it into the lane.wait / lane.steps /
                # lane.handoff children of the job's step span
                LANE_STAMPS_KEY: {"submitted": job.submitted_t,
                                  "admitted": job.admitted_t,
                                  "retired": time.perf_counter()},
            }
            # per-image UNet-eval accounting (ISSUE 12): full evals this
            # row actually paid over its WHOLE trajectory (the skipped
            # tally survives resume), observed once per row
            skipped = (int(self._h_skipped[job.slots[0]])
                       if self.reuse and job.slots else 0)
            evals = (job.steps - job.start_step) - skipped
            for _ in range(job.n_rows):
                _EVALS_PER_IMAGE.observe(evals)
            if self.reuse:
                info["unet_evals"] = evals
                info["steps_skipped"] = skipped
            # handoff BEFORE releasing the slots: busy() reports
            # "_pending or _handoff or _rows", so releasing first opens
            # a window where a draining caller sees an idle lane while
            # this job's future is still unresolved — drain() returning
            # True with the future pending was the at-seed stepper
            # flake on loaded single-core hosts
            self._handoff.append((job, pending, info))
            self._release_rows(job)
            changed = True
            self._sched._count(rows_completed=job.n_rows)
        for job in expired:
            # ordering discipline: the caller wakes on set_exception,
            # so everything it may read must land first (the expired
            # count) and the slots must stop counting toward busy()
            # only after the future resolves (the drain() gap above)
            self._sched._count(rows_expired=job.n_rows)
            if not job.future.done():
                job.future.set_exception(LaneDeadline(
                    f"row(s) of job {job.job_id} exceeded the in-lane "
                    f"deadline"))
            self._release_rows(job)
            changed = True
        if changed:
            with self._cond:
                self._cond.notify_all()

    def _maybe_checkpoint(self) -> None:
        """Snapshot every resident job's per-row state at this step
        boundary (every ``_ckpt_every`` steps) — to the worker's
        checkpoint spool when one is attached (fleet heartbeats, ISSUE
        6) and ALWAYS to the in-memory twin the guard's condemnation
        path resumes from (ISSUE 10). The snapshot is exact resume
        state: latents, carry PRNG keys, and multistep history at step
        k — restored rows continue on the bit-identical solo
        trajectory.

        The guard's per-row finite-check rides the SAME device->host
        transfer: a job whose latents went non-finite is poisoned — it
        retires with :class:`InvalidOutput` (no checkpoint, no decode,
        no upload) while its lane peers keep stepping. Runs in the
        driver thread, so the reads only stall THIS lane's pipeline (by
        one window drain), never the submitters."""
        validate = _guard.validation_enabled()
        want_ckpt = self._spool is not None or _guard.watchdog_enabled()
        # swarmlens (ISSUE 11): numerics probing rides the SAME
        # checkpoint-boundary device->host transfer — enabling the
        # lane_row probe forces the transfer even with durability and
        # the watchdog off (set CHIASWARM_STEPPER_CKPT_EVERY=1 for
        # per-step resolution when bisecting)
        numerics_on = _numerics.enabled_for("lane_row")
        if (self._ckpt_every <= 0 or self._dev is None
                or not (validate or want_ckpt or numerics_on)):
            return
        if self.steps_executed % self._ckpt_every:
            return
        jobs = {id(j): j for j in self._rows if j is not None}
        if not jobs:
            return
        # one transfer for the whole lane, sliced per job below
        x = np.asarray(self._dev["x"])
        keys = old = cache_u = cache_c = None
        written = 0
        poisoned: list[_RowJob] = []
        for job in jobs.values():
            sel = list(job.slots)
            if numerics_on:
                # per-row lane-state summaries, recorded BEFORE the
                # finite screen so a poisoned row's NaN step is on the
                # record; slot index doubles as the shard id, so a
                # sharded lane aligns row-for-row with its unsharded
                # twin in the bisect streams
                for s in sel:
                    _numerics.record_host(
                        "lane_row", x[s], step=int(self._h_idx[s]),
                        shard=s, note=str(job.job_id))
            if validate and not np.isfinite(x[sel]).all():
                poisoned.append(job)
                continue
            step = int(self._h_idx[sel[0]])
            if step <= job.start_step or step >= job.steps:
                continue  # nothing to resume yet / rows about to retire
            if not want_ckpt:
                continue
            if keys is None:
                keys = np.asarray(self._dev["keys"])
                old = np.asarray(self._dev["old"])
            state = {
                "version": 1, "kind": "lane",
                "step": step, "steps": int(job.steps),
                "rows": int(job.n_rows),
                "height": int(self.height), "width": int(self.width_px),
                "guidance": float(job.guidance),
                # workload identity (ISSUE 7b): a resumed img2img row
                # must rejoin the SAME truncated ladder; mask/known/hint
                # state re-derives from the redelivered job's own inputs
                "workload": str(job.workload),
                "start": int(job.start_step),
                "x": pack_array(x[sel]),
                "keys": pack_array(keys[sel]),
                "old": pack_array(old[sel]),
            }
            if self.reuse:
                # DeepCache resume state (ISSUE 12): the deep-feature
                # caches + validity + skipped tally ride the snapshot,
                # so a redelivered row replays the EXACT remaining
                # reuse decisions — bit-identical to the uninterrupted
                # run. The schedule itself is recorded for validation:
                # a tampered schedule must restart clean, never finish
                # a different trajectory under this job's identity.
                if cache_u is None:
                    cache_u = np.asarray(self._dev["cache_u"])
                    cache_c = np.asarray(self._dev["cache_c"])
                state.update({
                    "reuse_schedule": [int(j) for j in
                                       job.reuse_schedule],
                    "cache_u": pack_array(cache_u[sel]),
                    "cache_c": pack_array(cache_c[sel]),
                    "cache_ok": bool(self._h_cache_ok[sel[0]]),
                    "skipped": int(self._h_skipped[sel[0]]),
                })
            self._ckpt_mem[id(job)] = state
            if self._spool is None:
                continue
            try:
                self._spool.save(job.job_id, state)
                written += 1
            except OSError as exc:  # durability never fails the lane
                log.warning("checkpoint for job %s failed: %s",
                            job.job_id, exc)
        for job in poisoned:
            self._poison_rows(job)
        if written:
            self._sched._count(checkpoints_written=written)

    def _poison_rows(self, job: _RowJob) -> None:
        """Retire ONE job's rows as numerically poisoned (swarmguard,
        ISSUE 10): non-finite latents never decode, never upload, and
        never take the lane's other jobs down — the job's future fails
        with :class:`InvalidOutput`, which the executor envelopes as a
        non-fatal ``invalid_output`` (REDISPATCH_KINDS member: a
        lease-aware hive re-runs it on a different node)."""
        step = int(self._h_idx[job.slots[0]]) if job.slots else 0
        self._release_rows(job)
        self._sched._count(rows_invalid=job.n_rows)
        if not job.future.done():
            job.future.set_exception(InvalidOutput(
                f"job {job.job_id}: non-finite latents at step {step} — "
                f"row(s) retired without decoding"))
        log.error("lane %d: job %s poisoned (non-finite latents at step "
                  "%d); %d row(s) retired invalid_output, peers keep "
                  "stepping", self.lane_id, job.job_id, step, job.n_rows)
        with self._cond:
            self._cond.notify_all()

    def _flush_handoff(self, block: bool) -> None:
        """Resolve retired rows whose decoded images are resident. With
        ``block=False`` (rows still stepping) in-flight decodes stay
        queued — the overlap — and resolve at a later boundary; with
        ``block=True`` (lane idle) the driver waits them out."""
        while self._handoff:
            job, pending, info = self._handoff[0]
            images = pending.device_images
            ready = True
            if not block:
                is_ready = getattr(images, "is_ready", None)
                ready = bool(is_ready()) if callable(is_ready) else False
            if not ready:
                return
            images.block_until_ready()
            self._handoff.popleft()
            if not job.future.cancelled():
                self._resolve(job, pending, info)

    @staticmethod
    def _resolve(job: _RowJob, pending, info: dict[str, Any]) -> None:
        """Hand a retired job its decoded images, stamping when."""
        info[LANE_STAMPS_KEY]["resolved"] = time.perf_counter()
        job.future.set_result((pending, info))

    def _release_rows(self, job: _RowJob) -> None:
        for s in job.slots:
            self._rows[s] = None
            self._h_active[s] = False
        self._ckpt_mem.pop(id(job), None)
        if self._dev is not None:
            self._sync_tables()

    def _fail_all(self, exc: BaseException) -> None:
        err = exc if isinstance(exc, Exception) else LaneRetired(str(exc))
        # retired rows with in-flight decodes: their chip time is already
        # spent — deliver if the decode survives, fail otherwise
        while self._handoff:
            job, pending, info = self._handoff.popleft()
            try:
                pending.device_images.block_until_ready()
                if not job.future.done():
                    self._resolve(job, pending, info)
            except Exception:
                if not job.future.done():
                    job.future.set_exception(err)
        with self._cond:
            # retire BEFORE draining: a submit racing this failure must
            # see a dead lane (and open a fresh one), not append a job
            # whose future nobody will ever resolve
            self._retired = True
            jobs = {id(j): j for j in self._rows if j is not None}
            jobs.update({id(j): j for j in self._pending})
            self._pending.clear()
            for s in range(self.width):
                self._rows[s] = None
            self._h_active[:] = False
        failed_rows = 0
        for job in jobs.values():
            failed_rows += job.n_rows
            if not job.future.done():
                job.future.set_exception(err)
        if jobs:
            # remember (key, width) BEFORE collectors wake: note_oom may
            # run after _lane_done has already deregistered this lane
            self._sched._note_lane_failure(self.key, self.width)
            self._sched._count(rows_failed=failed_rows, lanes_failed=1)
            log.warning("lane %d failed (%s): %d row(s) bounced to the "
                        "per-job path", self.lane_id, err, failed_rows)
        self._dev = None
        self._window.clear()


class StepScheduler:
    """Owns the lanes of one slot; thread-safe submit/stats/drain."""

    def __init__(self, slot: Any = None) -> None:
        self.slot = slot
        self._lock = threading.Lock()
        self._lanes: dict[tuple, Lane] = {}
        self._width_limits: dict[tuple, int] = {}
        self._stats = collections.Counter()
        self._fault: list[tuple[int, BaseException]] = []
        self._total_steps = 0
        self._last_oom_incident = -1
        # (key -> width) of recently failed lanes: note_oom must still
        # find the lane that just died even after _lane_done removed it
        self._failed_lane_hints: dict[tuple, int] = {}
        # adaptive-width demand signal (ISSUE 7c): submissions feed the
        # rows/sec EWMA; the worker's poll loop leaves a short-lived
        # rows hint so lanes can grow BEFORE the formatted submissions
        # land — the poll-loop / step-boundary merge
        self._arrivals = _ArrivalEwma()
        self._poll_hint_rows = 0
        self._poll_hint_t = float("-inf")
        # overload control (ISSUE 9): the per-step lane-admission cap
        # the worker pushes while brownout holds, and the step-latency
        # EWMA the admission estimator floors its predictions with
        self._admission_cap: int | None = None
        self._step_ewma = 0.0
        _register_for_exit(self)

    # ---- policy ----

    def lane_width(self, height: int, width: int) -> int:
        """Pinned width (``CHIASWARM_STEPPER_LANE_WIDTH``) or the static
        slot-saturation heuristic: data width x the measured per-chip
        profitable batch, pow2-bucketed. With the adaptive controller on
        this is only the anchor for :meth:`width_bounds`."""
        env = os.environ.get(ENV_LANE_WIDTH, "").strip()
        if env:
            width_rows = int(env)
        else:
            from chiaswarm_tpu.core.compile_cache import (
                bucket_batch,
                single_chip_rows,
            )

            data_width = max(1, int(getattr(self.slot, "data_width", 1)))
            per_device = single_chip_rows({"height": height, "width": width})
            width_rows = bucket_batch(max(2, data_width * per_device))
        return max(1, width_rows)

    def width_bounds(self, height: int, width: int) -> tuple[int, int]:
        """(min, max) lane width for the adaptive controller. Defaults:
        1 to 4x the saturation heuristic (pow2, capped at the batch
        lattice maximum) — wide enough that the closed loop, not a
        static guess, decides how much padding a traffic mix pays.
        Pinned width collapses the range to a point."""
        from chiaswarm_tpu.core.compile_cache import bucket_batch

        if not adaptive_enabled():
            pinned = self.lane_width(height, width)
            return pinned, pinned
        env_min = os.environ.get(ENV_MIN_WIDTH, "").strip()
        env_max = os.environ.get(ENV_MAX_WIDTH, "").strip()
        lo = max(1, int(env_min)) if env_min else 1
        if env_max:
            hi = bucket_batch(min(128, max(1, int(env_max))))
        else:
            hi = bucket_batch(min(128, 4 * self.lane_width(height, width)))
        return min(lo, hi), max(lo, hi)

    def initial_width(self, rows: int, height: int, width: int) -> int:
        """A fresh lane opens at the smallest lattice bucket that holds
        the rows there is evidence for: its first job's, plus what a
        fresh poll hint still announces beyond the arrivals that burned
        it (so call this AFTER the job's own ``_note_arrival``). No
        constant headroom: a lone one-image job rides a width-1 lane
        and pays for no padding row (ISSUE 27); the controller follows
        demand from there. Row-sharded lanes never resize, so they open
        at a width the mesh's data axis divides."""
        from chiaswarm_tpu.core.compile_cache import bucket_batch

        lo, hi = self.width_bounds(height, width)
        if not adaptive_enabled():
            return hi
        want = int(rows) + self.demand_signal()[1]
        if shard_rows_enabled():
            want = max(want, int(getattr(self.slot, "data_width", 1)))
        return max(lo, bucket_batch(max(1, min(hi, want))))

    def row_deadline_s(self) -> float:
        return float(os.environ.get(ENV_ROW_DEADLINE, "600") or 600)

    # ---- demand signal (adaptive width, ISSUE 7c) ----

    def _note_arrival(self, rows: int) -> None:
        now = time.monotonic()
        with self._lock:
            self._arrivals.note(int(rows), now)
            # the hinted rows have (partly) landed as real submissions:
            # burn the hint down so lanes never count the same rows as
            # both pending AND hinted (which would over-grow the width)
            self._poll_hint_rows = max(0, self._poll_hint_rows - int(rows))
            rate = self._arrivals.rate(now)
        _ARRIVAL_RATE.set(rate)

    def note_poll(self, jobs: int, now: float | None = None) -> None:
        """Worker poll hook: a poll just returned ``jobs`` jobs, so that
        many rows are about to be formatted and submitted. Lanes read
        the hint at their next step boundary and can grow BEFORE the
        submissions land — the queue never waits out a full lane."""
        now = time.monotonic() if now is None else now
        with self._lock:
            self._poll_hint_rows = max(0, int(jobs))
            self._poll_hint_t = now

    def demand_signal(self, now: float | None = None) -> tuple[float, int]:
        """(arrival-rate EWMA rows/sec, fresh poll-hint rows) — read by
        lane drivers lock-free relative to the lanes (only the scheduler
        lock is taken, never a lane's)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            rate = self._arrivals.rate(now)
            hint = (self._poll_hint_rows
                    if now - self._poll_hint_t <= 2.0 else 0)
        _ARRIVAL_RATE.set(rate)
        return rate, hint

    def width_limit_for(self, key: tuple) -> int | None:
        """The OOM-halving width cap for ``key`` (note_oom), read by the
        lane driver before each boundary so limits recorded mid-flight
        clamp the very next resize decision."""
        with self._lock:
            return self._width_limits.get(key)

    # ---- overload control (ISSUE 9, node/overload.py) ----

    def set_admission_cap(self, rows: int | None) -> None:
        """Brownout rung: cap lane rows admitted per step boundary
        (None/0 = uncapped). Pushed by the worker on every poll and
        every shed while its overload controller holds brownout."""
        with self._lock:
            self._admission_cap = (None if not rows or int(rows) <= 0
                                   else int(rows))

    def admission_cap(self) -> int | None:
        with self._lock:
            return self._admission_cap

    def note_step_seconds(self, seconds: float) -> None:
        """Lane drivers feed each step's wall time; the EWMA rides
        ``stats()`` so the worker's admission estimator can floor a
        lane job's predicted service at steps x step-latency."""
        with self._lock:
            self._step_ewma = (float(seconds) if self._step_ewma <= 0.0
                               else self._step_ewma + 0.25 * (
                                   float(seconds) - self._step_ewma))

    def step_ewma(self) -> float:
        """The step-seconds EWMA (0.0 while cold) — shared by the
        overload estimator's lane floor and the guard's hang-budget and
        slow-step signals (serving/guard.py)."""
        with self._lock:
            return self._step_ewma

    def hang_budget(self) -> float | None:
        """Wall-clock budget the watchdog arms around one lane step
        dispatch (swarmguard, ISSUE 10): k x the step EWMA between the
        floor/ceiling knobs; the ceiling alone while cold, so a lane's
        first (compiling) call is never condemned. None = watchdog off
        (``CHIASWARM_GUARD=0``)."""
        from chiaswarm_tpu.serving.guard import (
            hang_budget_s,
            watchdog_enabled,
        )

        if not watchdog_enabled():
            return None
        with self._lock:
            ewma = self._step_ewma
        return hang_budget_s(ewma)

    def retire_lanes_for_owner(self, owner_id: int) -> int:
        """Eviction→lane-retire (ISSUE 9 satellite, ROADMAP item 4c
        residue): ask every lane built on the components object with
        ``id == owner_id`` to retire as soon as its rows drain — idle
        lanes free their device state on the next driver wakeup instead
        of after the idle grace. Returns the number of lanes asked."""
        with self._lock:
            lanes = [lane for key, lane in self._lanes.items()
                     if key and key[0] == owner_id]
        for lane in lanes:
            lane.request_retire()
        return len(lanes)

    # ---- submission ----

    def submit_request(self, pipe, *, prompt: str, negative_prompt: str = "",
                       steps: int = 30, guidance_scale: float = 7.5,
                       height: int | None = None, width: int | None = None,
                       rows: int = 1, seed: int = 0,
                       scheduler: str | None = None,
                       deadline_s: float | None = None,
                       job_id: Any = None,
                       resume: dict[str, Any] | None = None,
                       init_image: Any = None, strength: float = 0.8,
                       mask: Any = None,
                       controlnet: Any = None, control_image: Any = None,
                       control_scale: float = 1.0,
                       reuse_schedule: Any = None) -> Future:
        """Prepare a job's rows (tokenize, encode, ladder, initial noise
        — plus, per workload: init-latent VAE encode, latent-mask
        quantization, ControlNet hint embedding) and hand them to the
        matching lane. Returns a Future resolving to ``(PendingImages,
        lane_info)``; raises :class:`LaneReject` when the job cannot
        ride a lane.

        Workloads (ISSUE 7b): ``init_image`` makes the rows img2img —
        ``strength`` maps to a per-row denoise START index exactly as
        the solo program quantizes it; ``mask`` (with ``init_image``)
        makes them inpaint — the latent-grid mask + clean source
        latents ride as row state and the step program re-projects the
        kept region per step; ``controlnet`` (a ControlNetBundle, with
        ``control_image``) routes to a bundle-keyed control lane with
        the pre-embedded hint + ``control_scale`` per row.

        ``resume`` (a lane checkpoint from a redelivered job) replaces
        the fresh-noise prologue with the snapshotted latents, keys, and
        multistep history, splicing the rows in at the recorded step. An
        invalid/corrupt payload is rejected LOUDLY and the job restarts
        at step 0 — progress is expendable, trajectory integrity is
        not."""
        import jax
        import jax.numpy as jnp

        from chiaswarm_tpu.core.compile_cache import (
            bucket_batch,
            bucket_image_size,
            bucket_steps,
        )
        from chiaswarm_tpu.core.rng import key_for_seed
        from chiaswarm_tpu.pipelines.diffusion import (
            _resize_batch,
            latent_mask,
        )
        from chiaswarm_tpu.schedulers import make_sampling_schedule, resolve

        from chiaswarm_tpu.schedulers.sampling import FEWSTEP_KINDS

        fam = pipe.c.family
        if fam.kind != "sd" or fam.image_conditioned:
            raise LaneReject(f"family {fam.name!r} does not ride lanes")
        sampler = resolve(scheduler, prediction_type=fam.prediction_type)
        if float(guidance_scale) <= 1.0 and sampler.kind not in \
                FEWSTEP_KINDS:
            # few-step kinds are guidance-embedded (ISSUE 12): their
            # CFG-free mode rides lanes — the per-row combine selects
            # the pure conditional prediction for guidance <= 1 rows
            raise LaneReject("guidance <= 1 runs the solo (no-CFG) program")
        if mask is not None and init_image is None:
            raise LaneReject("inpainting requires an init image")
        if controlnet is not None and (control_image is None
                                       or mask is not None
                                       or init_image is not None):
            raise LaneReject("controlnet lanes take exactly a "
                             "conditioning image")
        height, width = bucket_image_size(int(height or fam.default_size),
                                          int(width or fam.default_size))
        steps = max(1, int(steps))
        try:
            cap = bucket_steps(steps)
        except ValueError as exc:
            raise LaneReject(str(exc)) from exc
        rows = max(1, int(rows))
        workload = ("controlnet" if controlnet is not None else
                    "inpaint" if mask is not None else
                    "img2img" if init_image is not None else "txt2img")
        # img2img strength -> start index: the solo program's exact
        # quantization (the shared helper), so a lane row executes the
        # identical truncated ladder
        start_step = 0
        if workload == "img2img":
            from chiaswarm_tpu.pipelines.diffusion import (
                img2img_start_index,
            )

            start_step = img2img_start_index(steps, strength)
        bounds_lo, bounds_hi = self.width_bounds(height, width)
        if rows > bounds_hi:
            raise LaneReject(
                f"{rows} rows exceed the lane width cap {bounds_hi}")
        # DeepCache (ISSUE 12): the per-job schedule engages only behind
        # the env switch and never alongside the ControlNet branch —
        # schedule-carrying jobs ride reuse-keyed lanes whose program
        # compiles the cache branch in; everything else keeps the plain
        # lane program untouched
        reuse: tuple[int, ...] = ()
        if reuse_schedule:
            from chiaswarm_tpu.pipelines.diffusion import (
                deepcache_enabled,
                normalize_reuse_schedule,
            )

            if deepcache_enabled() and controlnet is None:
                try:
                    reuse = normalize_reuse_schedule(
                        steps, reuse_schedule, start_step)
                except ValueError as exc:
                    # the solo path raises the canonical user error
                    raise LaneReject(str(exc)) from exc
        key = (id(pipe.c), height, width, cap, sampler,
               None if controlnet is None else id(controlnet),
               bool(reuse))
        self._note_arrival(rows)
        lane_rows = self.initial_width(rows, height, width)
        limit = self._width_limits.get(key)
        if limit is not None and limit < lane_rows:
            lane_rows = max(rows, limit)

        # the job's sigma ladder: a handful of eager jnp calls whose
        # np.asarray waits on the device (behind any step in flight)
        with span("schedule"):
            sched = make_sampling_schedule(pipe.noise_schedule, steps,
                                           sampler)
            sig = np.asarray(sched.sigmas, np.float32)
            ts = np.asarray(sched.timesteps, np.float32)

        resume_step = 0
        restored = None
        if resume is not None:
            try:
                resume_step, restored = self._validate_resume(
                    pipe, resume, steps=steps, rows=rows,
                    height=height, width=width,
                    guidance=float(guidance_scale),
                    start=start_step, workload=workload,
                    reuse_schedule=reuse)
            except ResumeReject as exc:
                log.error("resume state for job %s rejected (%s); "
                          "restarting at step 0", job_id, exc)
                self._count(resumes_rejected=1)
                resume_step, restored = 0, None

        t_prep = time.perf_counter()
        with span("encode", rows=rows, steps=steps), span("lane.encode"):
            eb = bucket_batch(rows)
            ids = [jnp.asarray(i)
                   for i in pipe._tokenize([prompt or ""] * eb)]
            neg = [jnp.asarray(i) for i in
                   pipe._tokenize([negative_prompt or ""] * eb)]
            ctx_u, ctx_c, pooled_u, pooled_c = pipe.stepper_encode_fn(
                batch=eb)(pipe.c.params, ids, neg)
            # workload row state: init latents encoded with the job's
            # OWN seed through the same batch-1 executable the solo run
            # uses (bitwise solo equality by construction); masks
            # quantize through the shared latent_mask helper; hints
            # pre-embed once per job (the solo hoisting, kept)
            init_rows = mask_rows = cond_rows = None
            if init_image is not None:
                init = np.asarray(init_image)
                if init.shape[:2] != (height, width):
                    init = _resize_batch(init, height, width)
                z = pipe.encode_init_image(init, height, width, int(seed))
                init_rows = jnp.repeat(z, rows, axis=0)
            if mask is not None:
                lh, lw = pipe._latent_hw(height, width)
                m = latent_mask(np.asarray(mask, np.float32), lh, lw,
                                fam.vae.downscale)
                mask_rows = jnp.repeat(
                    jnp.asarray(m)[None, :, :, None], rows, axis=0)
            if controlnet is not None:
                cond = np.asarray(control_image)
                as_u8 = cond.dtype == np.uint8
                if cond.shape[:2] != (height, width):
                    cond = _resize_batch(cond, height, width)
                cond = np.asarray(cond, np.float32)
                if as_u8 or cond.max() > 1.0:
                    cond = cond / 255.0
                emb = pipe.stepper_control_embed_fn(
                    height=height, width=width)(
                        controlnet.params["embed"],
                        jnp.asarray(np.clip(cond, 0.0, 1.0))[None])
                cond_rows = jnp.repeat(emb, rows, axis=0)
            cache_u0 = cache_c0 = None
            cache_ok0 = False
            skipped0 = 0
            if restored is not None:
                # redelivered rows: the context re-encodes (it is a pure
                # function of the prompt), but latents/keys/history come
                # back exactly as the dead worker checkpointed them
                carry_rows = jnp.asarray(restored["keys"])
                x0_rows = jnp.asarray(restored["x"])
                old_rows = jnp.asarray(restored["old"])
                if reuse and "cache_u" in restored:
                    # DeepCache resume: the deep caches + validity +
                    # skipped tally splice back in, so the remaining
                    # reuse decisions replay bit-identically
                    cache_u0 = jnp.asarray(restored["cache_u"])
                    cache_c0 = jnp.asarray(restored["cache_c"])
                    cache_ok0 = bool(restored["cache_ok"])
                    skipped0 = int(restored["skipped"])
            else:
                # per-row noise keys: fold the row index into the job's
                # seed — exactly the solo program's key derivation, so
                # every row matches its solo run bit-for-bit in key space
                keys = jnp.stack(
                    [jax.random.fold_in(key_for_seed(int(seed)), r)
                     for r in range(rows)] +
                    [key_for_seed(int(seed))] * (eb - rows))
                carry, x0 = pipe.stepper_row_init_fn(
                    batch=eb, height=height, width=width)(
                        keys, jnp.float32(sig[start_step]))
                carry_rows, x0_rows, old_rows = carry[:rows], x0[:rows], None
                if init_rows is not None:
                    # img2img/inpaint prologue: x = init + noise * sigma
                    # (row_init returned the noise term at sigma[start])
                    x0_rows = init_rows + x0_rows
        _LANE_ADMIT_SECONDS.observe(time.perf_counter() - t_prep)
        job = _RowJob(
            job_id=job_id, n_rows=rows, steps=steps,
            guidance=float(guidance_scale), sigmas=sig, timesteps=ts,
            ctx_u=ctx_u[:rows], ctx_c=ctx_c[:rows],
            pooled_u=None if pooled_u is None else pooled_u[:rows],
            pooled_c=None if pooled_c is None else pooled_c[:rows],
            keys0=carry_rows, x0=x0_rows,
            resume_step=resume_step, old0=old_rows,
            workload=workload, start_step=start_step,
            known0=init_rows if mask is not None else None,
            mask0=mask_rows, cond0=cond_rows,
            cscale=float(control_scale),
            reuse_schedule=reuse,
            cache_u0=cache_u0, cache_c0=cache_c0,
            cache_ok0=cache_ok0, skipped0=skipped0,
            deadline=time.monotonic() + (deadline_s if deadline_s is not None
                                         else self.row_deadline_s()))
        self._enqueue(key, pipe, job, lane_rows, height, width, cap, sampler,
                      control=controlnet, bounds=(bounds_lo, bounds_hi),
                      reuse=bool(reuse))
        return job.future

    def _validate_resume(self, pipe, resume: dict[str, Any], *,
                         steps: int, rows: int, height: int, width: int,
                         guidance: float, start: int = 0,
                         workload: str = "txt2img",
                         reuse_schedule: tuple[int, ...] = (),
                         ) -> tuple[int, dict[str, np.ndarray]]:
        """Check a redelivered job's checkpoint against the job it claims
        to resume; returns (step, restored host arrays) or raises
        :class:`ResumeReject`. Every field is hostile until proven
        consistent — the payload crossed two serializations and a worker
        death."""
        if resume.get("kind") != "lane":
            raise ResumeReject(
                f"not a lane checkpoint (kind={resume.get('kind')!r})")
        try:
            step = int(resume["step"])
            ck_steps = int(resume["steps"])
            ck_rows = int(resume["rows"])
            ck_h, ck_w = int(resume["height"]), int(resume["width"])
            ck_guidance = float(resume["guidance"])
            # pre-ISSUE-7 checkpoints carry no workload fields: they
            # could only have come from txt2img lanes, which is exactly
            # what the defaults assert
            ck_start = int(resume.get("start", 0))
            ck_workload = str(resume.get("workload", "txt2img"))
            x = unpack_array(resume["x"])
            keys = unpack_array(resume["keys"])
            old = unpack_array(resume["old"])
        except (KeyError, TypeError, ValueError) as exc:
            raise ResumeReject(f"corrupt payload: {exc}") from exc
        if not start < step < steps:
            raise ResumeReject(f"step {step} outside ({start}, {steps})")
        if (ck_start, ck_workload) != (start, workload):
            # a checkpoint stepped down a different ladder suffix (or a
            # different workload's trajectory) must not finish under
            # this job's identity — restart clean instead
            raise ResumeReject(
                f"workload mismatch: checkpoint is {ck_workload} from "
                f"step {ck_start}, job is {workload} from {start}")
        if (ck_steps, ck_rows) != (steps, rows):
            raise ResumeReject(
                f"job mismatch: checkpoint is {ck_rows} row(s) x "
                f"{ck_steps} step(s), job wants {rows} x {steps}")
        if (ck_h, ck_w) != (height, width):
            raise ResumeReject(
                f"size mismatch: checkpoint {ck_h}x{ck_w}, "
                f"job {height}x{width}")
        if ck_guidance != guidance:
            # latents stepped so far under a DIFFERENT guidance would
            # finish under this job's and deliver the wrong image as a
            # success — a mixed-up checkpoint must restart clean instead
            raise ResumeReject(
                f"guidance mismatch: checkpoint {ck_guidance}, "
                f"job {guidance}")
        lh, lw = pipe._latent_hw(height, width)
        ch = pipe.c.family.vae.latent_channels
        if x.shape != (rows, lh, lw, ch) or old.shape != x.shape:
            raise ResumeReject(
                f"latent shape {x.shape} != {(rows, lh, lw, ch)}")
        if x.dtype != np.float32 or old.dtype != np.float32:
            raise ResumeReject(
                f"latent dtype {x.dtype}/{old.dtype}, lanes carry float32")
        # the per-row carry keys must match the lane's key template in
        # FULL shape and dtype: a (rows,)-shaped or wrong-dtype keys
        # array would pass a first-axis check here only to explode
        # inside lane admission, where _fail_all takes every co-resident
        # job down with it
        from chiaswarm_tpu.core.rng import key_for_seed

        template = np.asarray(key_for_seed(0))
        if keys.shape != (rows,) + template.shape or \
                keys.dtype != template.dtype:
            raise ResumeReject(
                f"key array {keys.dtype}{keys.shape} != expected "
                f"{template.dtype}{(rows,) + template.shape}")
        restored: dict[str, Any] = {"x": x, "keys": keys, "old": old}
        # DeepCache identity (ISSUE 12): a checkpoint stepped under a
        # DIFFERENT reuse schedule walked a different trajectory — it
        # must not finish under this job's identity. Tampered schedules
        # and missing/corrupt cache state restart clean.
        try:
            ck_reuse = tuple(int(j) for j in
                             (resume.get("reuse_schedule") or ()))
        except (TypeError, ValueError) as exc:
            raise ResumeReject(
                f"corrupt reuse_schedule: {exc}") from exc
        if ck_reuse != tuple(reuse_schedule):
            raise ResumeReject(
                f"reuse-schedule mismatch: checkpoint {list(ck_reuse)}, "
                f"job {list(reuse_schedule)}")
        if reuse_schedule:
            try:
                cache_u = unpack_array(resume["cache_u"])
                cache_c = unpack_array(resume["cache_c"])
                cache_ok = bool(resume["cache_ok"])
                skipped = int(resume["skipped"])
            except (KeyError, TypeError, ValueError) as exc:
                raise ResumeReject(
                    f"corrupt DeepCache state: {exc}") from exc
            c1 = pipe.c.family.unet.block_out_channels[1]
            cache_dtype = np.dtype(pipe.c.unet.dtype)
            want = (rows, lh, lw, c1)
            if cache_u.shape != want or cache_c.shape != want:
                raise ResumeReject(
                    f"deep-cache shape {cache_u.shape} != {want}")
            if cache_u.dtype != cache_dtype or \
                    cache_c.dtype != cache_dtype:
                raise ResumeReject(
                    f"deep-cache dtype {cache_u.dtype}, lanes carry "
                    f"{cache_dtype}")
            if not 0 <= skipped < steps:
                raise ResumeReject(
                    f"skipped tally {skipped} outside [0, {steps})")
            restored.update(cache_u=cache_u, cache_c=cache_c,
                            cache_ok=cache_ok, skipped=skipped)
        return step, restored

    def _enqueue(self, key, pipe, job, lane_rows, height, width, cap,
                 sampler, control=None, bounds=None,
                 reuse: bool = False) -> None:
        created = False
        with self._lock:
            lane = self._lanes.get(key)
            # a lane narrower than the job could never admit it and
            # _admit_locked is FIFO — the job (and everything behind it)
            # would starve while the lane stays busy. An adaptive lane
            # grows to fit at its next boundary, UNLESS an OOM width cap
            # holds it below the job's rows; a pinned lane never grows.
            # Either way out: open a fresh, wide-enough lane — the old
            # one drains its residents and idles out.
            if lane is not None and lane.width < job.n_rows:
                limit = self._width_limits.get(key)
                can_grow = lane._adaptive and (limit is None
                                               or limit >= job.n_rows)
                if not can_grow:
                    lane = None
            if lane is None or not lane.try_enqueue(job):
                lane = Lane(self, key, pipe, width=lane_rows, height=height,
                            width_px=width, steps_cap=cap, sampler=sampler,
                            control=control, width_bounds=bounds,
                            reuse=reuse)
                self._lanes[key] = lane
                created = True
                if not lane.try_enqueue(job):  # pragma: no cover
                    raise LaneRetired("fresh lane refused the job")
        if created:  # outside the lock: _count takes it too
            self._count(lanes_created=1)

    # ---- lifecycle / observability ----

    def _lane_done(self, lane: Lane) -> None:
        with self._lock:
            if self._lanes.get(lane.key) is lane:
                del self._lanes[lane.key]

    def _note_lane_failure(self, key: tuple, width: int) -> None:
        with self._lock:
            self._failed_lane_hints[key] = int(width)
            while len(self._failed_lane_hints) > 32:  # bounded
                self._failed_lane_hints.pop(
                    next(iter(self._failed_lane_hints)))

    def note_oom(self) -> None:
        """Degradation-ladder hook: after an OOM'd lane run, future lanes
        rebuild at half width (the burst analog splits and re-runs
        serially, node/worker.py). Limits are sticky for the process —
        a chip that OOM'd once at width W will OOM again. Halves ONCE
        per lane incident: every resident job's collector reports the
        same failure, and N jobs must not shrink the width 2^N-fold."""
        with self._lock:
            incident = self._stats.get("lanes_failed", 0)
            if incident == self._last_oom_incident:
                return
            self._last_oom_incident = incident
            keys = (set(self._lanes) | set(self._width_limits)
                    | set(self._failed_lane_hints))
            for key in keys:
                cur = self._width_limits.get(key)
                if cur is None:
                    lane = self._lanes.get(key)
                    cur = (lane.width if lane is not None
                           else self._failed_lane_hints.get(key, 2))
                self._width_limits[key] = max(1, cur // 2)

    def _count(self, **kw: int) -> None:
        with self._lock:
            for k, v in kw.items():
                if v:
                    self._stats[k] += v
            self._total_steps = self._stats.get("steps_executed", 0)

    def _maybe_fault(self, lane: Lane) -> None:
        """Chaos seam (tests/test_chaos.py): raise a scripted fault inside
        the driver loop once the scheduler has executed N total steps."""
        if not self._fault:
            return
        with self._lock:
            if self._fault and self._total_steps >= self._fault[0][0]:
                _, exc = self._fault.pop(0)
                raise exc

    def inject_fault(self, after_steps: int, exc: BaseException) -> None:
        with self._lock:
            self._fault.append((int(after_steps), exc))

    def stats(self) -> dict[str, Any]:
        now = time.monotonic()
        with self._lock:
            data = dict(self._stats)
            lanes = list(self._lanes.values())
            rate = self._arrivals.rate(now)
            step_ewma = self._step_ewma
        active = sum(lane.occupancy()[0] for lane in lanes)
        width = sum(lane.occupancy()[1] for lane in lanes)
        steps_a = data.get("row_steps_active", 0)
        steps_p = data.get("row_steps_padded", 0)
        denom = max(1, steps_a + steps_p)
        data.update({
            "lanes_live": len(lanes),
            "rows_active": active,
            "lane_rows_total": width,
            "lane_occupancy": round(steps_a / denom, 4),
            "padding_waste": round(steps_p / denom, 4),
            "arrival_rate": round(rate, 4),
            "step_seconds_ewma": round(step_ewma, 6),
        })
        return data

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Wait for every lane to go empty (in-flight rows finish, pending
        rows admitted and finished). True when drained."""
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            with self._lock:
                lanes = list(self._lanes.values())
            if not any(lane.busy() for lane in lanes):
                return True
            time.sleep(0.01)
        return False

    def shutdown(self, timeout_s: float = 5.0) -> None:
        """Stop every lane; unfinished rows fail with LaneRetired so their
        jobs bounce to the per-job path (or envelope) — never lost."""
        with self._lock:
            lanes = list(self._lanes.values())
        for lane in lanes:
            lane.stop()
        for lane in lanes:
            lane.join(timeout_s)


_EXIT_SCHEDULERS: "weakref.WeakSet[StepScheduler]"


def _register_for_exit(sched: StepScheduler) -> None:
    """Stop every lane at interpreter exit: a daemon driver thread still
    dispatching XLA programs during teardown aborts the process with a
    C++ ``terminate`` on this backend."""
    global _EXIT_SCHEDULERS
    try:
        _EXIT_SCHEDULERS.add(sched)
        return
    except NameError:
        pass
    import atexit
    import weakref

    _EXIT_SCHEDULERS = weakref.WeakSet()
    _EXIT_SCHEDULERS.add(sched)

    @atexit.register
    def _stop_all_lanes() -> None:
        for scheduler in list(_EXIT_SCHEDULERS):
            try:
                scheduler.shutdown(timeout_s=2.0)
            except Exception:  # teardown must never raise
                pass


def aggregate_stats(steppers) -> dict[str, Any]:
    """Merge several schedulers' stats (one per slot) for /healthz:
    counters sum, the occupancy/waste ratios recompute from the summed
    row-step totals."""
    total = collections.Counter()
    rate = step_ewma = 0.0
    for stepper in steppers:
        for key, value in stepper.stats().items():
            if key == "arrival_rate":
                rate = max(rate, value)  # EWMAs do not sum
                continue
            if key == "step_seconds_ewma":
                step_ewma = max(step_ewma, value)
                continue
            if key in ("lane_occupancy", "padding_waste"):
                continue
            total[key] += value
    steps_a = total.get("row_steps_active", 0)
    steps_p = total.get("row_steps_padded", 0)
    denom = max(1, steps_a + steps_p)
    data = dict(total)
    data["lane_occupancy"] = round(steps_a / denom, 4)
    data["padding_waste"] = round(steps_p / denom, 4)
    data["arrival_rate"] = round(rate, 4)
    data["step_seconds_ewma"] = round(step_ewma, 6)
    return data


def retire_lanes_for_owner(owner_id: int) -> int:
    """Process-wide eviction→lane-retire hook: ask EVERY scheduler's
    lanes built on the components object ``id(c) == owner_id`` to
    retire at drain (idle lanes retire immediately). Called by the
    residency ledger when it evicts a model (serving/residency.py) so
    the lane's device state — the last holder of the evicted params —
    frees at eviction, not after the idle grace."""
    try:
        schedulers = list(_EXIT_SCHEDULERS)
    except NameError:  # no StepScheduler was ever constructed
        return 0
    return sum(sched.retire_lanes_for_owner(owner_id)
               for sched in schedulers)


_ATTACH_LOCK = threading.Lock()


def get_stepper(slot: Any) -> StepScheduler:
    """The slot's resident StepScheduler (created on first use). Lanes —
    not the slot depth semaphore — serialize lane traffic; the slot is
    only consulted for its mesh data width."""
    with _ATTACH_LOCK:
        stepper = getattr(slot, "_stepper", None)
        if stepper is None:
            stepper = StepScheduler(slot)
            try:
                slot._stepper = stepper
            except (AttributeError, TypeError):  # exotic slot stubs
                pass
        return stepper
