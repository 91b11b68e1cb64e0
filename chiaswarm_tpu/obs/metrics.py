"""Prometheus-style metrics registry: counters, gauges, histograms.

The worker grew three generations of ad-hoc telemetry — the resilience
counters (PR 2), the stepper lane stats (PR 3), and the seed's bare
``/healthz`` dict. This module is the one vocabulary they all migrate
onto: a :class:`Registry` of named metrics with label support, rendered
in the Prometheus text exposition format at ``/metrics``
(node/worker.py) and snapshot as JSON into ``/healthz`` (which stays a read-through view for back-compat).

Design constraints, in order:

- **stdlib only** (like ``analysis/``): importable with no jax, no
  aiohttp — the linter, host tools, and ``core/compile_cache.py`` all
  load it.
- **allocation-light on the hot path**: an ``inc()``/``observe()`` is a
  dict lookup + float add under one lock; no per-event objects.
- **hermetic**: :class:`Registry` is a class, not only a module global.
  Each Worker owns its own registry (multiple hermetic workers share a
  test process; their counters must not bleed into each other), while
  process-wide machinery (the compile cache, lane step timing) uses the
  shared :data:`REGISTRY`. ``render_all`` merges both for ``/metrics``.

Counters are monotonic. For sources that already maintain their own
monotonic totals (the stepper's lane stats), a *collector* callback
registered via :meth:`Registry.add_collector` mirrors them in at scrape
time with :meth:`Counter.set_to` — the Prometheus collect-on-scrape
pattern, not a license to decrement.
"""

from __future__ import annotations

import logging
import math
import threading
from typing import Any, Callable, Iterable, Sequence

log = logging.getLogger("chiaswarm.obs")

#: default histogram buckets (seconds): spans poll blips (~ms) through
#: cold XLA compiles (~minutes). Callers with tighter ranges pass their
#: own.
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
                   600.0)


def _escape_label(value: str) -> str:
    return (value.replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _escape_help(value: str) -> str:
    return value.replace("\\", "\\\\").replace("\n", "\\n")


def _format_value(value: float) -> str:
    if value == math.inf:
        return "+Inf"
    if value == -math.inf:
        return "-Inf"
    if float(value).is_integer():
        return str(int(value))
    return repr(float(value))


class _Metric:
    """Base: one named family holding a value per label-values tuple."""

    typ = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = ()) -> None:
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._values: dict[tuple[str, ...], float] = {}
        if not self.labelnames:
            # unlabeled series exist from registration, so /metrics shows
            # an explicit 0 instead of omitting the family entirely
            self._values[()] = 0.0

    def _key(self, labels: dict[str, Any]) -> tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name!r} takes labels {self.labelnames}, "
                f"got {tuple(sorted(labels))}")
        return tuple(str(labels[n]) for n in self.labelnames)

    def value(self, **labels: Any) -> float:
        with self._lock:
            return self._values.get(self._key(labels), 0.0)

    def series(self) -> dict[tuple[str, ...], float]:
        with self._lock:
            return dict(self._values)

    # ---- exposition ----

    def _series_name(self, suffix: str, key: tuple[str, ...],
                     extra: tuple[tuple[str, str], ...] = ()) -> str:
        pairs = tuple(zip(self.labelnames, key)) + extra
        if not pairs:
            return f"{self.name}{suffix}"
        inner = ",".join(f'{n}="{_escape_label(v)}"' for n, v in pairs)
        return f"{self.name}{suffix}{{{inner}}}"

    def render(self) -> list[str]:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {_escape_help(self.help)}")
        lines.append(f"# TYPE {self.name} {self.typ}")
        series = self.series()
        for key in sorted(series):
            lines.append(f"{self._series_name('', key)} "
                         f"{_format_value(series[key])}")
        return lines

    def snapshot(self) -> dict[str, Any]:
        return {"type": self.typ, "help": self.help,
                "values": {",".join(k) if k else "": v
                           for k, v in sorted(self.series().items())}}


class Counter(_Metric):
    """Monotonic counter. ``inc`` adds; ``set_to`` mirrors an external
    monotonic total in (collector use only — never goes backward)."""

    typ = "counter"

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def set_to(self, value: float, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = max(self._values.get(key, 0.0),
                                    float(value))


class Gauge(_Metric):
    typ = "gauge"

    def set(self, value: float, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels: Any) -> None:
        self.inc(-amount, **labels)


class Histogram(_Metric):
    """Fixed-bucket histogram (cumulative ``le`` buckets + sum/count)."""

    typ = "histogram"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS) -> None:
        super().__init__(name, help, labelnames)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket")
        self._counts: dict[tuple[str, ...], list[int]] = {}
        self._sums: dict[tuple[str, ...], float] = {}
        self._totals: dict[tuple[str, ...], int] = {}
        self._values.clear()  # histograms expose bucket/sum/count instead

    def observe(self, value: float, **labels: Any) -> None:
        key = self._key(labels)
        value = float(value)
        with self._lock:
            counts = self._counts.get(key)
            if counts is None:
                counts = self._counts[key] = [0] * len(self.buckets)
                self._sums[key] = 0.0
                self._totals[key] = 0
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    counts[i] += 1
                    break
            self._sums[key] += value
            self._totals[key] += 1

    def count(self, **labels: Any) -> int:
        with self._lock:
            return self._totals.get(self._key(labels), 0)

    def sum(self, **labels: Any) -> float:
        with self._lock:
            return self._sums.get(self._key(labels), 0.0)

    def percentile(self, q: float, **labels: Any) -> float | None:
        """Estimate the q-quantile (q in [0, 1]) from the bucket counts
        by linear interpolation inside the covering bucket — the
        Prometheus ``histogram_quantile`` estimate, computed locally.

        Returns None for an empty series. Mass above the last finite
        bucket clamps to that bound (the estimate cannot exceed what
        the buckets resolve), so pick buckets that cover the tail you
        care about. This is the primitive behind the measured
        hang-budget suggestion (serving/guard.py, ISSUE 11)."""
        key = self._key(labels)
        with self._lock:
            # COPY under the lock: a concurrent observe() mutates the
            # bucket list in place, and iterating the live list against
            # a stale total skews the interpolation
            counts = list(self._counts.get(key) or ())
            total = self._totals.get(key, 0)
        if not counts or total <= 0:
            return None
        q = min(max(float(q), 0.0), 1.0)
        rank = q * total
        cum = 0
        for i, n in enumerate(counts):
            if not n:
                continue
            lo = self.buckets[i - 1] if i else 0.0
            hi = self.buckets[i]
            if cum + n >= rank:
                frac = (rank - cum) / n
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
            cum += n
        return self.buckets[-1]  # overflow mass: clamp to the last bound

    def percentiles(self, qs: Sequence[float] = (0.5, 0.9, 0.99),
                    **labels: Any) -> dict[str, float] | None:
        """{"p50": ..., "p90": ..., ...} or None when empty."""
        out = {}
        for q in qs:
            v = self.percentile(q, **labels)
            if v is None:
                return None
            out[f"p{str(round(q * 100, 1)).rstrip('0').rstrip('.')}"] = v
        return out

    def render(self) -> list[str]:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {_escape_help(self.help)}")
        lines.append(f"# TYPE {self.name} {self.typ}")
        with self._lock:
            items = [(k, list(c), self._sums[k], self._totals[k])
                     for k, c in sorted(self._counts.items())]
        for key, counts, total_sum, total in items:
            cum = 0
            for bound, n in zip(self.buckets, counts):
                cum += n
                lines.append(
                    f"{self._series_name('_bucket', key, (('le', _format_value(bound)),))} "
                    f"{cum}")
            lines.append(
                f"{self._series_name('_bucket', key, (('le', '+Inf'),))} "
                f"{total}")
            lines.append(f"{self._series_name('_sum', key)} "
                         f"{_format_value(total_sum)}")
            lines.append(f"{self._series_name('_count', key)} {total}")
        return lines

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "type": self.typ,
                "help": self.help,
                "buckets": list(self.buckets),
                "values": {
                    ",".join(k) if k else "": {
                        "counts": list(c),
                        "sum": self._sums[k],
                        "count": self._totals[k],
                    }
                    for k, c in sorted(self._counts.items())
                },
            }


class Registry:
    """Named metric families + scrape-time collector callbacks.

    ``counter``/``gauge``/``histogram`` are get-or-create: asking twice
    for the same name returns the same object (so modules can declare
    their metrics independently), but re-declaring with a different type
    or label set is a programming error and raises.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, _Metric] = {}
        self._collectors: list[Callable[[], None]] = []

    def _get_or_create(self, cls, name: str, help: str,
                       labelnames: Sequence[str], **kwargs) -> Any:
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if (type(existing) is not cls
                        or existing.labelnames != tuple(labelnames)):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{type(existing).__name__}{existing.labelnames}")
                return existing
            metric = cls(name, help, labelnames, **kwargs)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def get(self, name: str) -> _Metric | None:
        with self._lock:
            return self._metrics.get(name)

    def add_collector(self, fn: Callable[[], None]) -> None:
        """Register a callback run before every render/snapshot — the
        place to mirror externally-maintained state (lane stats, queue
        depths, breaker states) into gauges/counters at scrape time."""
        with self._lock:
            self._collectors.append(fn)

    def collect(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:  # a broken mirror must never break scrapes
                log.exception("metrics collector failed")

    def _sorted_metrics(self) -> list[_Metric]:
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    def render(self) -> str:
        """Prometheus text exposition format (version 0.0.4)."""
        self.collect()
        lines: list[str] = []
        for metric in self._sorted_metrics():
            lines.extend(metric.render())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict[str, Any]:
        """JSON-able view of every family — the programmatic twin of
        ``render()``."""
        self.collect()
        return {m.name: m.snapshot() for m in self._sorted_metrics()}


def render_all(registries: Iterable[Registry]) -> str:
    """Concatenate several registries' expositions (the worker's own
    registry + the process-global one) into one scrape body."""
    return "".join(r.render() for r in registries)


#: process-global registry: compile-cache activity, lane step timing —
#: state that is genuinely one-per-process. Worker-scoped counters live
#: on the worker's own Registry instance instead (hermetic tests).
REGISTRY = Registry()

#: occupancy-ratio buckets: one per eighth of the lane, matching the
#: pow2 lane widths (a 16-wide lane quantizes occupancy to sixteenths;
#: eighths keep the histogram readable at every width)
OCCUPANCY_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)


def lane_occupancy_histogram(registry: Registry | None = None) -> Histogram:
    """Per-lane occupancy ratio (active rows / lane width), sampled at
    every lane step by serving/stepper.py and exposed at ``/metrics``.

    THE padding-efficiency signal for lane-width tuning: a lane stepping
    at 0.25 occupancy spends 3/4 of its batched UNet FLOPs on padding
    rows, which the scalar ``padding_waste`` ratio in ``/healthz`` only
    shows as a long-run average — the histogram shows whether waste is a
    steady trickle (width too large for the arrival rate) or admission
    bursts draining out (width fine, arrivals lumpy).

    Labeled by lane WIDTH, not lane id: widths come from the bounded
    pow2 bucket lattice, while lane ids increment for every rebuilt lane
    — id labels on the process-global registry would leak one series
    family per retired lane forever (Prometheus cardinality 101)."""
    return (registry or REGISTRY).histogram(
        "chiaswarm_stepper_lane_occupancy_ratio",
        "active rows / lane width at each lane step, by lane width",
        labelnames=("width",),
        buckets=OCCUPANCY_BUCKETS)

#: resume-step buckets: pow2 over the step-capacity lattice
#: (core/compile_cache.py bucket_steps caps at 128)
RESUME_STEP_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


def resume_step_histogram(registry: Registry | None = None) -> Histogram:
    """Step index at which redelivered rows splice back into a lane
    (ISSUE 6), observed by serving/stepper.py at admission.

    THE fleet-invariant proof signal: a redelivered job that resumed
    records step >= 1 here (and in its result's
    ``pipeline_config.stepper.resume_step``); a distribution stuck at
    low steps means leases expire faster than the checkpoint cadence
    (``CHIASWARM_STEPPER_CKPT_EVERY``) can push progress — lengthen the
    lease or tighten the cadence. Unlabeled: lane identity would leak
    unbounded series (same cardinality rule as the occupancy family)."""
    return (registry or REGISTRY).histogram(
        "chiaswarm_stepper_resume_step",
        "step index at which resumed (redelivered) rows spliced into "
        "a lane",
        buckets=RESUME_STEP_BUCKETS)


def lane_resizes_counter(registry: Registry | None = None) -> Counter:
    """Adaptive-width control-loop actions (ISSUE 7): lanes growing or
    shrinking their row file at a step boundary, labeled by direction.

    The closed loop's activity signal: a healthy loop resizes a handful
    of times as traffic regime shifts; a high rate means the controller
    is thrashing (occupancy oscillating around a threshold — raise the
    patience knob or pin ``CHIASWARM_STEPPER_LANE_WIDTH``). Direction
    split matters: all-grow means demand keeps outrunning capacity
    (raise ``CHIASWARM_STEPPER_MAX_WIDTH``), all-shrink means the
    initial width is habitually too large."""
    return (registry or REGISTRY).counter(
        "chiaswarm_stepper_lane_resizes_total",
        "adaptive lane-width resizes at step boundaries, by direction",
        labelnames=("direction",))


def arrival_rate_gauge(registry: Registry | None = None) -> Gauge:
    """The lane scheduler's arrival-rate EWMA (rows/second), the demand
    half of the adaptive-width control signal (occupancy is the supply
    half). Sampled at each control decision; 0 when lanes are idle."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_stepper_arrival_rate",
        "EWMA of lane row arrivals per second (adaptive-width demand "
        "signal)")


def lane_admissions_counter(registry: Registry | None = None) -> Counter:
    """Rows admitted into lanes, by workload (ISSUE 7: lanes serve
    img2img/inpaint/controlnet alongside txt2img). The eligibility-
    breadth proof: a workload stuck at 0 while its jobs flow means it is
    falling back to the per-job path (check LaneReject logs)."""
    return (registry or REGISTRY).counter(
        "chiaswarm_stepper_lane_admissions_total",
        "lane rows admitted, by workload kind",
        labelnames=("workload",))


# ---- step-collapse families (ISSUE 12, swarmturbo) ----
#
# The 15x headline gap is steps x full-UNet; these families measure the
# collapse of that product directly. Incremented by BOTH execution
# paths — the lane driver per dispatch (serving/stepper.py) and the
# solo submit per job (pipelines/diffusion.py) — on the process-global
# REGISTRY, pre-seeded at import by those modules.

#: how one per-row UNet evaluation was served: ``full`` runs the whole
#: network (and refreshes the DeepCache deep-feature cache when reuse
#: is compiled in); ``reuse`` replays the cached deep activation and
#: recomputes only the shallow level-0 blocks
STEPPER_UNET_EVAL_MODES = ("full", "reuse")

#: per-image UNet-eval buckets: pow2 over the step-capacity lattice —
#: a 30-step baseline lands in (16, 32]; the 4-step few-step family in
#: (2, 4]; DeepCache-on rows land wherever their refresh cadence puts
#: the full-eval count
UNET_EVAL_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


def unet_evals_counter(registry: Registry | None = None) -> Counter:
    """Per-row UNet evaluations by mode (``full`` vs DeepCache
    ``reuse``). THE step-collapse cost signal: the full-mode rate IS
    the chip-time driver (a reuse eval costs only the shallow level-0
    blocks), so full/(full+reuse) is the fraction of the old per-step
    cost the traffic still pays."""
    return (registry or REGISTRY).counter(
        "chiaswarm_stepper_unet_evals_total",
        "per-row UNet evaluations, by mode (full vs DeepCache reuse)",
        labelnames=("mode",))


def steps_skipped_counter(registry: Registry | None = None) -> Counter:
    """Denoise steps whose deep UNet blocks were skipped via DeepCache
    feature reuse (per row). Zero with ``CHIASWARM_DEEPCACHE`` off or
    no per-job ``reuse_schedule`` — a zero here while reuse jobs flow
    means misaligned lane mates kept forcing full evals (check the
    lane admission mix)."""
    return (registry or REGISTRY).counter(
        "chiaswarm_stepper_steps_skipped_total",
        "denoise steps served from the DeepCache deep-feature cache "
        "(per row)")


def unet_evals_per_image_histogram(
        registry: Registry | None = None) -> Histogram:
    """FULL UNet evaluations each finished image actually paid —
    observed once per row at retirement (lanes) or submit (solo). The
    distribution the ≥4x step-collapse acceptance reads: a 30-step
    baseline observes 30, the lcm 4-step family 4, DeepCache rows
    their refresh count."""
    return (registry or REGISTRY).histogram(
        "chiaswarm_stepper_unet_evals_per_image",
        "full UNet evaluations per finished image",
        buckets=UNET_EVAL_BUCKETS)


# ---- HBM model-residency families (ISSUE 8, serving/residency.py) ----
#
# The residency manager owns the ledger; these helpers only declare the
# families (on the process-global REGISTRY by default — the manager is
# one-per-process like the compile cache; hermetic test managers pass
# their own Registry). The manager pre-seeds every label vocabulary at
# import so dashboards see zeroes from the first scrape (the ISSUE-6
# convention for the lease/resume families).

#: the authoritative per-model state vocabulary (registry + residency,
#: ISSUE 8 satellite: quarantine and residency share one enum)
RESIDENCY_STATES = ("cold", "loading", "resident", "degraded",
                    "evicted", "unavailable", "quarantined")

#: why a resident model was dropped from HBM
RESIDENCY_EVICT_REASONS = ("capacity", "squeeze")

#: how a model load was served (resident admit / degraded load-per-job /
#: background prefetch)
RESIDENCY_LOAD_MODES = ("resident", "per_job", "prefetch")


def residency_bytes_gauge(registry: Registry | None = None) -> Gauge:
    """Bytes of model params the residency ledger holds resident —
    MEASURED from the live trees at load (summed shard .nbytes), not
    estimated. The headroom signal: steady-state near the budget with a
    nonzero eviction rate means the catalog is HBM-bound (quantize, or
    raise CHIASWARM_RESIDENCY_BUDGET)."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_residency_resident_bytes",
        "measured bytes of model params currently resident in HBM")


def residency_budget_gauge(registry: Registry | None = None) -> Gauge:
    return (registry or REGISTRY).gauge(
        "chiaswarm_residency_budget_bytes",
        "HBM byte budget the residency ledger evicts down to")


def residency_peak_gauge(registry: Registry | None = None) -> Gauge:
    """High-water mark of resident + reserved bytes — THE no-double-
    buffer proof: a swap that evicts before loading keeps this at
    most budget + one model (the churn tests assert exactly that)."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_residency_peak_bytes",
        "high-water mark of resident + in-flight reserved bytes")


def residency_models_gauge(registry: Registry | None = None) -> Gauge:
    """Model count per residency state (the /healthz ``models`` enum,
    aggregated). ``degraded`` > 0 is the graceful-degradation rung in
    action: some model serves load-per-job because its measured
    footprint exceeds the budget."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_residency_models",
        "models per residency state (cold/loading/resident/degraded/"
        "evicted/unavailable/quarantined)",
        labelnames=("state",))


def residency_evictions_counter(registry: Registry | None = None) -> Counter:
    """Ledger evictions by reason: ``capacity`` (donation — room made
    for an incoming load) vs ``squeeze`` (the budget itself shrank). A
    high capacity rate with a small catalog means footprints ~ budget:
    expect swap latency on every model switch."""
    return (registry or REGISTRY).counter(
        "chiaswarm_residency_evictions_total",
        "models evicted from HBM residency, by reason",
        labelnames=("reason",))


def residency_loads_counter(registry: Registry | None = None) -> Counter:
    """Model loads by mode. ``per_job`` counting up is the degradation
    rung burning load latency per job — the signal to quantize
    (CHIASWARM_WEIGHTS=int8) or grow the budget; ``prefetch`` counts
    idle-poll warm loads driven by the per-model arrival EWMA."""
    return (registry or REGISTRY).counter(
        "chiaswarm_residency_loads_total",
        "model param-tree loads, by residency mode",
        labelnames=("mode",))


def residency_bounces_counter(registry: Registry | None = None) -> Counter:
    """Jobs refused because the model cannot fit even transiently
    (footprint > hard limit): uploaded as non-fatal
    ``model_unavailable`` so a lease-aware hive redispatches them
    (node/minihive.py REDISPATCH_KINDS)."""
    return (registry or REGISTRY).counter(
        "chiaswarm_residency_bounces_total",
        "jobs bounced model_unavailable: model cannot fit transiently")


def residency_load_seconds_histogram(
        registry: Registry | None = None) -> Histogram:
    """Wall time of one model load (convert/build + measure), by mode —
    with ``swapped="1"`` when the load had to evict first."""
    return (registry or REGISTRY).histogram(
        "chiaswarm_residency_load_seconds",
        "model load wall time, by residency mode and whether the load "
        "evicted residents first",
        labelnames=("mode", "swapped"),
        buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                 60.0, 120.0, 300.0))


# ---- overload-control families (ISSUE 9, node/overload.py) ----
#
# Declared here like the residency families; the controller is
# per-WORKER (hermetic test workers must not bleed shed counts into
# each other), so these take the worker's registry and the controller
# pre-seeds every label vocabulary at construction.

#: overload controller states (the brownout rung ladder)
OVERLOAD_STATES = ("normal", "brownout")


def overload_state_gauge(registry: Registry | None = None) -> Gauge:
    """Overload-control state: 0 = normal, 1 = brownout (sustained
    shedding tripped the rung — lane admissions are capped per step and
    the shed margin tightens until sheds stop for the cooldown)."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_overload_state",
        "overload control state (0=normal, 1=brownout)")


def overload_shed_counter(registry: Registry | None = None) -> Counter:
    """Jobs shed at admission because the estimator predicted a
    deadline miss, by workload. Sheds upload as non-fatal ``overloaded``
    envelopes a lease-aware hive redispatches (with this worker
    excluded) — a rising rate means offered load exceeds this node's
    capacity; compare against ``chiaswarm_jobs_total{outcome="ok"}`` to
    read the admitted fraction."""
    return (registry or REGISTRY).counter(
        "chiaswarm_overload_shed_total",
        "jobs shed by deadline-aware admission control, by workload",
        labelnames=("workload",))


def overload_backpressure_counter(
        registry: Registry | None = None) -> Counter:
    """Poll-loop waits inserted by queue-depth backpressure: the worker
    predicted its queued backlog alone would outlast the backpressure
    budget and stopped asking for MORE work. Jobs already queued keep
    executing — backpressure throttles intake, shedding handles what
    was already admitted."""
    return (registry or REGISTRY).counter(
        "chiaswarm_overload_backpressure_waits_total",
        "poll-loop waits inserted by queue-depth backpressure")


def overload_predicted_wait_histogram(
        registry: Registry | None = None) -> Histogram:
    """The admission estimator's predicted completion time (queue drain
    + service estimate) sampled at every shed decision. Compare the
    distribution against the deadline knobs: mass past the deadline IS
    the shed rate; mass near it means the margin is doing the work."""
    return (registry or REGISTRY).histogram(
        "chiaswarm_overload_predicted_wait_seconds",
        "admission estimator's predicted completion time at each "
        "shed decision",
        buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                 120.0, 300.0, 600.0, 1800.0))


def overload_admission_cap_gauge(
        registry: Registry | None = None) -> Gauge:
    """Current brownout lane-admission cap (rows per step boundary);
    0 = uncapped (normal state). Pushed into every slot's
    StepScheduler (serving/stepper.py) while brownout holds."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_overload_admission_cap",
        "brownout cap on lane rows admitted per step boundary "
        "(0 = uncapped)")


# ---- swarmguard families (ISSUE 10, serving/guard.py) ----
#
# Declared here like the overload families; the DeviceGuard is
# per-WORKER (hermetic test workers must not bleed health events into
# each other), takes the worker's registry, and pre-seeds every
# enumerable label vocabulary at construction. The ``model`` and
# ``device`` labels are bounded by the catalog / chip count, not by
# time (the occupancy-family cardinality rule).


def guard_hangs_counter(registry: Registry | None = None) -> Counter:
    """Compiled calls the watchdog declared hung, by phase (``lane``
    step dispatch vs ``solo`` denoise). A nonzero rate is THE
    gray-failure signal: the chip wedges without dying — check the
    device health gauge to see whether one chip owns the hangs."""
    return (registry or REGISTRY).counter(
        "chiaswarm_guard_hangs_total",
        "compiled calls declared hung by the step watchdog, by phase",
        labelnames=("phase",))


def guard_condemned_counter(registry: Registry | None = None) -> Counter:
    """Lanes condemned by the watchdog: each one is a lane-rebuild heal
    rung — the condemned lane's rows re-admit to a freshly built lane,
    resuming from their last step-boundary checkpoint."""
    return (registry or REGISTRY).counter(
        "chiaswarm_guard_condemned_lanes_total",
        "lanes condemned by the hang watchdog (rows re-admitted to a "
        "fresh lane)")


def guard_invalid_counter(registry: Registry | None = None) -> Counter:
    """Rows retired with ``invalid_output`` (non-finite latents or a
    poisoned decoded frame), by model. One model owning the count while
    others stay clean points at the checkpoint; every model counting
    together points at the device (watch the health gauge)."""
    return (registry or REGISTRY).counter(
        "chiaswarm_guard_invalid_outputs_total",
        "jobs retired invalid_output instead of uploading a poisoned "
        "image, by model",
        labelnames=("model",))


def guard_device_health_gauge(registry: Registry | None = None) -> Gauge:
    """Per-device health score in [0, 1]: 1 = healthy, decays with the
    consecutive hang/slow-step/invalid-output streak and recovers with
    OK events. The ladder rungs quote their thresholds in streak units;
    the gauge is the operator-facing normalization."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_guard_device_health",
        "per-device health score (1 = healthy; ladder rungs fire as "
        "the sickness streak grows)",
        labelnames=("device",))


def guard_heal_rung_counter(registry: Registry | None = None) -> Counter:
    """Healing-ladder escalations by rung: ``lane_rebuild`` (every
    condemnation), ``cache_flush`` (executable LRU dropped),
    ``device_quarantine`` (mesh shrunk to the healthy chips), and
    ``restart`` (graceful drain + the distinct supervisor exit code)."""
    return (registry or REGISTRY).counter(
        "chiaswarm_guard_heal_rung_total",
        "self-healing ladder escalations, by rung",
        labelnames=("rung",))


def guard_quarantined_gauge(registry: Registry | None = None) -> Gauge:
    return (registry or REGISTRY).gauge(
        "chiaswarm_guard_quarantined_devices",
        "devices currently quarantined out of the serving mesh")


# ---- swarmsight families (ISSUE 13, obs/flight.py) ----


def trace_spans_evicted_counter(
        registry: Registry | None = None) -> Counter:
    """Spans dropped from the bounded trace ring by eviction — the
    signal that a scraper polling ``/debug/traces`` too slowly is
    LOSING trace data, not that there is none. Pair with the endpoint's
    ``?since=<seq>`` cursor: a gap between the scraper's last seq and
    the ring's oldest seq is exactly this eviction window."""
    return (registry or REGISTRY).counter(
        "chiaswarm_trace_spans_evicted_total",
        "spans evicted from the bounded trace ring before any scrape "
        "collected them (use /debug/traces?since= to detect gaps)")


# ---- swarmdurable families (ISSUE 14, node/hivelog.py) ----
#
# Worker-side: the hive-session outage families live on each worker's
# registry (hermetic, like guard/overload). Hive-side journal families
# live on the hive's own registry (node/minihive.py) — /api/stats is
# their scrape, not /metrics.

#: when a dead-letter envelope was replayed back into the upload queue:
#: ``startup`` (the PR-2 path — the worker process restarted) vs
#: ``live`` (ISSUE 14 — the hive healed mid-run and the spool drained
#: without a restart)
DEAD_LETTER_REPLAY_WHEN = ("startup", "live")


def dead_letter_replayed_counter(
        registry: Registry | None = None) -> Counter:
    """Dead-letter envelopes re-queued for upload, split by when: a
    ``live`` count rising during an incident is the ride-through
    working (spooled chip time landing the moment the hive heals); a
    ``startup`` count means the outage outlived the worker process.
    Complements ``chiaswarm_results_replayed_total`` (the undifferen-
    tiated PR-2 total, kept for dashboard compatibility)."""
    return (registry or REGISTRY).counter(
        "chiaswarm_dead_letter_replayed_total",
        "dead-letter results re-queued for upload, by replay moment",
        labelnames=("when",))


def hive_session_state_gauge(registry: Registry | None = None) -> Gauge:
    """The worker's hive-session state: 0 = online, 1 = OUTAGE
    ride-through (leases assumed lost, in-flight work completing,
    results spooling). THE page-the-operator signal for a hive-side
    incident as seen from the fleet's edge — every worker's gauge
    flipping together is a hive outage; one worker alone is a
    partition."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_hive_session_state",
        "worker's hive reachability state (0=online, 1=outage)")


def hive_shard_session_state_gauge(
        registry: Registry | None = None) -> Gauge:
    """The per-shard half of the session signal (swarmfed, ISSUE 17):
    a multiplexed worker holds one HiveSession per hive shard, and this
    family shows exactly WHICH shard's traffic is riding through an
    outage while the rest keep serving. The unlabeled gauge above stays
    the page-the-operator any-shard-down rollup (shard-0-equivalent on
    a single-hive worker)."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_hive_shard_session_state",
        "worker's per-shard hive session (0=online, 1=outage)",
        ("shard",))


# ---- fleet-planner families (swarmplan, ISSUE 19, node/planner.py) ----
#
# The autoscaler's control loop is hive-side state, so the families
# live on the planner's registry (the hive's, usually) — and like the
# residency/overload families every label vocabulary pre-seeds at
# planner construction (plus once at module import for the global
# registry) so a dashboard sees zeros before the first decision.

#: which way a planning tick moved the target
PLANNER_DIRECTIONS = ("up", "down", "hold")

#: why the tick chose that direction — ``demand`` (the smoothed
#: arrival rate moved the capacity target), ``backlog`` (the hive-side
#: queue added a drain term), ``hysteresis`` (inside the deadband),
#: ``cooldown`` (a recent actuation pinned the fleet), ``bounds``
#: (min/max fleet clamp engaged), ``steady`` (target == actual)
PLANNER_REASONS = ("demand", "backlog", "hysteresis", "cooldown",
                   "bounds", "steady")


def planner_target_workers_gauge(registry: Registry | None = None) -> Gauge:
    """The planner's current target fleet size — what the supervisor
    contract (``GET /api/plan``) tells a real deployment to converge
    on. Persistent gap vs the actual gauge below means actuation is
    lagging (slow cold starts: ROADMAP item 5) or the supervisor is
    not consuming the plan."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_planner_target_workers",
        "fleet size the planner wants (the /api/plan target)")


def planner_actual_workers_gauge(registry: Registry | None = None) -> Gauge:
    """Live, reachable workers the planner observed on its last tick
    (the /api/fleet ``workers_live`` view it planned against)."""
    return (registry or REGISTRY).gauge(
        "chiaswarm_planner_actual_workers",
        "live workers observed by the planner's last tick")


def planner_decisions_counter(registry: Registry | None = None) -> Counter:
    """Planning-tick decisions by direction and reason. A high
    ``up``+``down`` churn rate with ``reason="demand"`` means the
    hysteresis band or cooldowns are too tight for the arrival noise;
    mostly ``hold/steady`` is a converged loop."""
    return (registry or REGISTRY).counter(
        "chiaswarm_planner_decisions_total",
        "planning-tick decisions, by direction and reason",
        labelnames=("direction", "reason"))


def planner_placement_moves_counter(
        registry: Registry | None = None) -> Counter:
    """Per-worker model assignments that CHANGED between consecutive
    plans (the placement half of the loop). Each move costs a survivor
    a warm load — a sustained rate here with flat fleet size means the
    demand mix is churning faster than residency can follow."""
    return (registry or REGISTRY).counter(
        "chiaswarm_planner_placement_moves_total",
        "per-worker model placement assignments changed by a new plan")


def planner_worker_hours_counter(
        registry: Registry | None = None) -> Counter:
    """Accumulated worker-hours as the planner observes them (actual
    fleet size x tick interval). THE cost side of the autoscaler's
    headline: the ISSUE-19 gate compares this against every static
    roster in the swept set."""
    return (registry or REGISTRY).counter(
        "chiaswarm_planner_worker_hours_total",
        "worker-hours accumulated under the planner's watch")


# ---- text-generation families (ISSUE 29, pipelines/text.py) ----
#
# Process-global like the compile-cache families, and fed after a job
# from values its two programs RETURN (token counts are shapes; the
# routing counts come back as scalars of the prefill and decode
# programs) - no host callback runs inside a jit.

#: tokens through the text programs: prompt tokens prefilled, and rows x
#: new tokens decoded (bucket padding included: it is computed)
TEXT_TOKENS = REGISTRY.counter(
    "chiaswarm_text_tokens_total",
    "tokens through the text programs, by phase",
    labelnames=("phase",))

#: (token, expert) pairs the router chose, split by whether the expert
#: is held on this chip (the others' share is left out by design)
MOE_ROUTED_PAIRS = REGISTRY.counter(
    "chiaswarm_moe_routed_pairs_total",
    "routed (token, expert) pairs, by phase and by whether this chip "
    "holds the expert",
    labelnames=("phase", "held"))

#: distinct held experts with at least one token, summed over decode
#: steps and expert layers: with the held decode pairs it gives the
#: tokens an expert sees a step, and the expert weights a step reads
MOE_EXPERTS_HIT = REGISTRY.counter(
    "chiaswarm_moe_experts_hit_total",
    "distinct held experts hit, summed over decode steps and layers")

#: what the hit count was summed over: decode steps x expert layers (hits
#: over this = distinct held experts one layer reads in one step)
MOE_LAYER_STEPS = REGISTRY.counter(
    "chiaswarm_moe_layer_steps_total",
    "decode steps x expert layers the experts-hit count was summed over")

#: key blocks of the attention layers' prefill: those the causal kernel's
#: bound admits at each chunk's position ("yes") against the rest of the
#: cache's capacity ("no"; of a sliding layer's local buffer), per
#: attention layer; from what the host knows of a job (positions, chunk,
#: capacity, the kernel's block). It counts memory traffic only: what a
#: block that is not read costs in grid steps is the next family's
TEXT_PREFILL_KEY_BLOCKS = REGISTRY.counter(
    "chiaswarm_text_prefill_key_blocks_total",
    "key blocks of the attention layers' prefill, by whether the causal "
    "kernel's bound admits them and they are fetched or they lie past "
    "the written cache and are not (a fetch saved, not a grid step: see "
    "chiaswarm_text_prefill_block_steps_total)",
    labelnames=("read",))

#: grid steps of the attention layers' prefill kernels by what the kernel
#: does in them, summed over the grid's heads, query blocks, chunks and
#: layers (``ops/causal_flash_attention.py::block_steps``, the kernel's
#: own tests on host integers): "whole" = a block pair scored without a
#: mask; "diagonal" = the sub-tiles scored in the pairs the diagonal or a
#: window's edge crosses (a pair masked whole counts as one); "dead" = a
#: step entered and left with nothing to do, above the diagonal or below
#: every window (0.25 us each on a v5e: PERF.md, PR 36)
TEXT_PREFILL_BLOCK_STEPS = REGISTRY.counter(
    "chiaswarm_text_prefill_block_steps_total",
    "grid steps of the attention layers' prefill kernels: block pairs "
    "scored whole, sub-tiles scored where the diagonal crosses a pair, "
    "and steps that hold no work",
    labelnames=("kind",))

#: key blocks of the decode's sweep over the prompt's shared entries
#: (latents, or a full layer's keys and values): those up to the prompt's
#: length, which the kernel reads ("yes"), against the rest of the
#: capacity, which its clamped index map leaves ("no"); per such layer
#: and decode step, from what the host knows of a job (prompt, steps,
#: capacity, the block)
TEXT_DECODE_KEY_BLOCKS = REGISTRY.counter(
    "chiaswarm_text_decode_key_blocks_total",
    "key blocks of the decode's sweep over the shared prompt, by whether "
    "they hold a prompt token and are read or lie past the prompt and "
    "are not",
    labelnames=("read",))

#: query-key pairs one head scores in the softmax-attention layers: a
#: prompt token against the tokens up to itself (prefill), a decode step
#: against the prompt and the row's suffix up to its own entry, in a
#: sliding layer only those inside the window; summed over those layers,
#: chunks or steps, and rows, from host integers
TEXT_ATTENTION_PAIRS = REGISTRY.counter(
    "chiaswarm_text_attention_pairs_total",
    "query-key pairs a head scores in the softmax-attention layers "
    "(under the window in a sliding layer), by phase",
    labelnames=("phase",))

#: query-key pairs of the sliding-window layers, a head: "visible" =
#: inside the window (what ``attention_pairs`` counts for those layers),
#: "scored" = what is computed for them, masked or not: every key of
#: every block the windowed kernel steps in the prefill, the window's
#: and the suffix's slots a decode step scores; summed over sliding
#: layers, chunks or steps, and rows, from host integers. scored /
#: visible = 1 is a sweep that steps only what the window shows
TEXT_WINDOW_PAIRS = REGISTRY.counter(
    "chiaswarm_text_window_pairs_total",
    "query-key pairs of the sliding-window layers a head, by whether "
    "they are inside the window (visible) or computed for it (scored)",
    labelnames=("kind",))

#: sub-blocks of the delta-rule prefill's in-chunk matrices, by how
#: ``kda_chunked`` builds them: "pairwise" decays on the diagonal,
#: "product" of rescaled factors left of it; per KDA layer, prefill chunk
#: and sub-chunk, from what the host knows of a job (tokens, chunk sizes)
TEXT_KDA_BLOCKS = REGISTRY.counter(
    "chiaswarm_text_kda_blocks_total",
    "sub-blocks of the delta-rule prefill's in-chunk matrices, by "
    "whether they are built from pairwise decays or as a product",
    labelnames=("form",))

#: bytes of each kind of cache the last decode held
TEXT_CACHE_BYTES = REGISTRY.gauge(
    "chiaswarm_text_cache_bytes",
    "cache bytes of the last text decode, by kind (latent / recurrent / "
    "full / window)",
    labelnames=("kind",))


#: the Prometheus text exposition content type
CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"
