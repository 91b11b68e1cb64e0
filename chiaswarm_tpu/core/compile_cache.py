"""Resident compiled-pipeline cache with shape bucketing.

The reference reloads model weights from disk on every job
(swarm/diffusion/diffusion_func.py:41-46) — tolerable on CUDA where module
construction is cheap. On TPU, XLA compilation dominates: recompiling a
denoise loop per job (or per odd image size) is fatal to throughput. This
component has no reference analog and exists precisely because of the XLA
compilation model (SURVEY.md §7 "hard parts" #3):

- **Shape bucketing**: arbitrary requested resolutions/batch sizes snap to a
  small lattice of compiled shapes (latent sizes multiple of 64px at the
  image level, batch in powers of two). One compiled executable serves every
  job that lands in its bucket.
- **Param residency**: converted model weights stay on device between jobs,
  keyed by (model_name, dtype), LRU-evicted under an HBM budget.
- **Executable LRU**: jitted pipeline callables keyed by
  (model key, static config, bucketed shapes).

Thread-safe; the worker's executor threads share one cache per process.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from pathlib import Path
from typing import Any, Callable, Hashable

from chiaswarm_tpu.obs.metrics import REGISTRY

_POW2 = (1, 2, 4, 8, 16, 32, 64, 128)

# ---- swarmscope hooks (chiaswarm_tpu/obs) ---------------------------------
# A runtime recompile is the R6 lint hazard made flesh: a shape/config that
# escaped the bucketing lattice silently costs seconds-to-minutes of chip
# time. These counters make every executable-cache miss — and the duration
# of the compile it triggered — visible on /metrics, labeled by the program
# tag (generate / stepper_step / encode / ...).

_CACHE_HITS = REGISTRY.counter(
    "chiaswarm_compile_cache_hits_total",
    "compile-cache lookups served from residency",
    labelnames=("cache", "tag"))
_CACHE_MISSES = REGISTRY.counter(
    "chiaswarm_compile_cache_misses_total",
    "compile-cache misses (each one built/loaded its value)",
    labelnames=("cache", "tag"))
_BUILD_SECONDS = REGISTRY.histogram(
    "chiaswarm_compile_cache_build_seconds",
    "time spent building a missed cache entry (trace/convert/load)",
    labelnames=("cache", "tag"))
_COMPILE_SECONDS = REGISTRY.histogram(
    "chiaswarm_compile_seconds",
    "first-call duration of a freshly built executable — the XLA "
    "trace+compile cost a cache miss actually paid",
    labelnames=("tag",))
_COMPILES = REGISTRY.counter(
    "chiaswarm_compiles_total",
    "executables compiled at runtime (cache-miss first calls); a "
    "nonzero rate after warmup means a shape escaped the buckets (R6)",
    labelnames=("tag",))


def _key_tag(key: Hashable) -> str:
    """Program tag from a static_cache_key-shaped key (owner, tag, ...);
    foreign key shapes fall into one bucket."""
    if isinstance(key, tuple) and len(key) >= 2 and isinstance(key[1], str):
        return key[1]
    return "other"


def _instrument_executable(fn: Any, tag: str) -> Any:
    """Time a fresh executable's FIRST call into the compile histogram.

    jax.jit compiles lazily, so the LRU-miss factory only builds the
    wrapper — the XLA work happens on first invocation. The first call
    includes one execution too; compile dominates it by orders of
    magnitude on real programs, and one timed call per executable
    lifetime costs nothing after."""
    if not callable(fn):
        return fn
    state = {"timed": False}

    def wrapped(*args: Any, **kwargs: Any) -> Any:
        if state["timed"]:
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        state["timed"] = True  # benign race: worst case two observations
        _COMPILE_SECONDS.observe(time.perf_counter() - t0, tag=tag)
        _COMPILES.inc(tag=tag)
        return out

    return wrapped


def xla_compiler_options() -> dict[str, str] | None:
    """Extra per-executable XLA:TPU compiler options from the
    ``CHIASWARM_XLA_OPTIONS`` env var ("key=value,key2=value2").

    Passed as ``compiler_options`` to the pipelines' TOP-LEVEL ``jax.jit``
    calls (nested jits reject them). The main production knob is
    ``xla_tpu_scoped_vmem_limit_kib`` — the default ~16 MiB scoped VMEM
    caps the flash-attention block sweep and conv fusion buffer sizes.
    No option name is verified on this chip (ROADMAP, Maintenance)."""
    raw = os.environ.get("CHIASWARM_XLA_OPTIONS", "").strip()
    if not raw:
        return None
    return dict(kv.split("=", 1) for kv in raw.split(",") if "=" in kv)


#: Env knobs that change what a pipeline TRACES (swarmkey / ISSUE 20):
#: attention impl selection and ring threshold are read at trace time
#: (ops/attention.py), the flash block/VMEM knobs are frozen into module
#: constants at import (ops/flash_attention.py), ring-flash mode picks
#: the fused vs scan program (ops/ring_flash_attention.py), and the XLA
#: options change the compiled artifact itself. Every name here is
#: folded into static_cache_key ONLY-WHEN-SET — with all knobs unset the
#: key stays byte-identical to the historical tuple, so default
#: deployments keep every warm slot (the taps-off stance from ISSUE 11).
#: CHIASWARM_NUMERICS / CHIASWARM_ACTIVATIONS are deliberately absent:
#: those already fold their own richer fingerprints conditionally below.
_TRACE_ENV_KNOBS = (
    "CHIASWARM_ATTENTION",
    "CHIASWARM_RING_MIN_TOKENS",
    "CHIASWARM_RING_FLASH",
    "CHIASWARM_FLASH_BLOCK_Q",
    "CHIASWARM_FLASH_BLOCK_KV",
    "CHIASWARM_FLASH_VMEM_MB",
    "CHIASWARM_XLA_OPTIONS",
)


def _trace_knobs() -> tuple:
    """The set-and-nonempty trace-affecting knobs as a sorted-by-table
    ((name, value), ...) vector — empty tuple in a default environment,
    so callers can fold it only-when-set."""
    return tuple((name, os.environ[name].strip())
                 for name in _TRACE_ENV_KNOBS
                 if os.environ.get(name, "").strip())


def cache_fingerprint() -> tuple:
    """Cross-process executable-identity handle for the AOT artifact
    cache (ROADMAP item 5): compiler provenance (jax/jaxlib/plugin
    versions) plus the trace-affecting knob vector.

    The in-process key (``static_cache_key``) may embed ``id()``-based
    owners — stable within a process, meaningless outside it. A
    serialized artifact needs the opposite: every component stable
    across processes and machines (R20's jurisdiction). Versions come
    from package metadata, not ``jax.__version__``, so the lint tier can
    import this module without jax."""
    import importlib.metadata

    versions = []
    for dist in ("jax", "jaxlib", "libtpu", "libtpu-nightly"):
        try:
            versions.append((dist, importlib.metadata.version(dist)))
        except Exception:  # absent plugin: fingerprint just omits it
            continue
    return ("chiaswarm-exec-v1", tuple(versions), ("knobs", _trace_knobs()))


def artifact_cache_key(tag: str, static: dict) -> tuple:
    """Content-addressed key for a SHIPPED executable artifact: the
    persistent fingerprint plus the owner-free static key. The
    in-process owner id is dropped by construction — it can never leak
    into a serialized artifact's identity."""
    return (cache_fingerprint(),) + static_cache_key(0, tag, static)[1:]


def toplevel_jit(fn, **kwargs):
    """``jax.jit`` for the pipelines' end-to-end programs, with the
    env-configured compiler options applied."""
    import jax

    opts = xla_compiler_options()
    if opts:
        kwargs.setdefault("compiler_options", opts)
    return jax.jit(fn, **kwargs)


def enable_persistent_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache; returns its directory.

    The in-process LRU below amortizes compiles within one worker
    lifetime; this amortizes them ACROSS restarts (an SDXL-1024 lane is
    minutes of compile cold, seconds from the cache). The directory is
    placed from OUTSIDE the program and is stable, because a cache that
    moves never hits:

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself —
      this function sets no directory at all;
    - unset: ``<checkout>/.jax_cache`` (the package's parent, the same
      anchor ``native/`` uses for ``csrc/``) — never ``~``, a temp name,
      a pid or a time.

    The worker, tests/conftest and chip_smoke call this and nothing
    else in the package touches the setting (``perfbench/run.py`` sets
    the variable itself, to the same directory). Failures raise: a
    worker that cannot persist its compiles must say so at start-up."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not cache_dir:
        cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    return cache_dir


_ROOT_ENV_VARS = ("SWARM_TPU_ROOT", "SDAAS_ROOT")


def settings_root() -> Path:
    """Resolve the settings directory (reference: swarm/settings.py:53-64).
    Here, beside the other placement this package makes, because the
    residency ledger below ``node/`` persists under it too."""
    for var in _ROOT_ENV_VARS:
        root = os.environ.get(var)
        if root:
            return Path(root).expanduser()
    return Path.home() / ".swarm-tpu"


def static_cache_key(owner: int, tag: str, static: dict) -> tuple:
    """Hashable executable-cache key from a pipeline's static build args.

    Shared by every pipeline's ``_get_fn`` (diffusion/upscale/cascade/
    audio) so dataclass-valued statics (sampler configs, ...) normalize the
    same way everywhere — including nested dataclasses and containers.

    swarmlens (ISSUE 11): while ``CHIASWARM_NUMERICS`` enables any
    probe, the live tap fingerprint is appended — a program traced with
    taps must never be served to (or from) a taps-off cache slot, and a
    probe-filter change retraces. With numerics OFF (the default) the
    key is byte-identical to the historical 3-tuple, so the taps-off
    invariance gate can hold trivially."""

    def norm(v: Any) -> Hashable:
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            return tuple(sorted(
                (f.name, norm(getattr(v, f.name)))
                for f in dataclasses.fields(v)))
        if isinstance(v, dict):
            return tuple(sorted((k, norm(x)) for k, x in v.items()))
        if isinstance(v, (list, tuple)):
            return tuple(norm(x) for x in v)
        return v

    key = (owner, tag, tuple(sorted((k, norm(v))
                                    for k, v in static.items())))
    from chiaswarm_tpu.obs import numerics

    if numerics.enabled():
        key = key + (("numerics", numerics.fingerprint()),)

    # low-precision activations (ISSUE 18): same stance as the numerics
    # fingerprint — CHIASWARM_ACTIVATIONS changes what the program
    # traces (fake-quant seams at attention q/k/v and the UNet block
    # inputs), so an enabled format must never share an executable slot
    # with the fp trace; with the knob OFF the key stays byte-identical
    from chiaswarm_tpu.convert import quantize

    if quantize.activations_enabled():
        key = key + (("activations", quantize.activations_format()),)

    # trace-affecting env knobs (swarmkey / ISSUE 20): same only-when-set
    # stance — a knob flip must retrace, a default environment must keep
    # its historical byte-identical key (and every warm slot with it)
    knobs = _trace_knobs()
    if knobs:
        key = key + (("knobs", knobs),)
    return key


def bucket_batch(n: int) -> int:
    """Round batch up to the next power of two (caps recompiles at
    log2(max_batch) executables per pipeline)."""
    if n < 1:
        raise ValueError("batch must be >= 1")
    for p in _POW2:
        if n <= p:
            return p
    raise ValueError(f"batch {n} exceeds supported maximum {_POW2[-1]}")


def single_chip_rows(kwargs: dict[str, Any]) -> int:
    """How many batch rows ONE device is given for this job class: 4 up
    to 512 x 512 px, else 1. Reached by the DIFFUSION workflows only
    (the executor's burst key, the worker's drain and the lane's width
    anchor). The rule was set on an installation that is gone; the
    ledger's only reading of rows on this chip is PR 27's — a width-2
    lane step cost 2.07x (1024 px) / 2.18x (512 px) a width-1 step — so
    the 4 is unverified here (ROADMAP S1b). Size comes from the explicit
    kwargs or, for img2img/inpaint jobs that take the image's own grid,
    the fetched image shape; otherwise assumed large."""
    try:
        h, w = int(kwargs.get("height") or 0), int(kwargs.get("width") or 0)
    except (TypeError, ValueError):
        return 1
    if not (h and w):
        image = kwargs.get("image")
        if image is not None and getattr(image, "ndim", 0) >= 2:
            h, w = int(image.shape[0]), int(image.shape[1])
    return 4 if 0 < h * w <= 512 * 512 else 1


_STEP_BUCKETS = (16, 32, 64, 128)


def bucket_steps(n: int) -> int:
    """Round a denoise step count up to the lane capacity lattice.

    The step scheduler (serving/stepper.py) compiles ONE resident step
    program per lane whose per-row sigma/timestep tables are sized to
    this capacity; bucketing keeps the lane-program count bounded while
    letting jobs with different step counts share a lane. The step
    program executes one step per call, so capacity padding costs table
    memory only — never compute."""
    if n < 1:
        raise ValueError("steps must be >= 1")
    for cap in _STEP_BUCKETS:
        if n <= cap:
            return cap
    raise ValueError(
        f"steps {n} exceeds the lane capacity maximum {_STEP_BUCKETS[-1]}")


def bucket_image_size(height: int, width: int, *, multiple: int = 64,
                      min_size: int = 64, max_size: int = 1024) -> tuple[int, int]:
    """Snap a requested image size onto the compiled lattice.

    Mirrors the reference's size clamp (swarm/job_arguments.py:14,96-102 caps
    at 1024x1024; small sizes are honored — only a MAX clamp exists there)
    but additionally quantizes to ``multiple`` so XLA sees a bounded shape
    set. Images are generated at the bucketed size and
    center-cropped/resized on host to the exact request when they differ.
    ``multiple=64`` keeps SD latents divisible by 8, so any bucket survives
    the UNet's downsampling path.
    """

    def snap(v: int) -> int:
        v = max(min_size, min(max_size, v))
        return ((v + multiple - 1) // multiple) * multiple

    return snap(height), snap(width)


@dataclasses.dataclass
class _Entry:
    value: Any
    size_bytes: int


class LruCache:
    """A byte-budgeted LRU used for both param trees and executables."""

    def __init__(self, budget_bytes: int | None = None, max_items: int | None = None,
                 kind: str = "cache"):
        self._budget = budget_bytes
        self._max_items = max_items
        self._kind = kind  # /metrics label: "params" / "executables"
        self._entries: collections.OrderedDict[Hashable, _Entry] = collections.OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get_or_create(self, key: Hashable, factory: Callable[[], Any],
                      size_bytes: int = 0,
                      size_of: Callable[[Any], int] | None = None) -> Any:
        """``size_of`` computes the entry's byte size from the built value
        (for factories whose footprint is only known after loading)."""
        tag = _key_tag(key)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                hit = True
            else:
                self.misses += 1
                hit = False
        if hit:
            _CACHE_HITS.inc(cache=self._kind, tag=tag)
            return entry.value
        _CACHE_MISSES.inc(cache=self._kind, tag=tag)
        # Build outside the lock: factories compile/convert and can take
        # minutes; concurrent misses on the *same* key are rare (jobs for one
        # model serialize on the slot) and harmless (last write wins).
        t0 = time.perf_counter()
        value = factory()
        _BUILD_SECONDS.observe(time.perf_counter() - t0,
                               cache=self._kind, tag=tag)
        if size_of is not None:
            size_bytes = size_of(value)
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                # concurrent miss on the same key: keep the first result and
                # drop ours, so byte accounting stays exact.
                self._entries.move_to_end(key)
                return existing.value
            self._entries[key] = _Entry(value, size_bytes)
            self._bytes += size_bytes
            self._evict_locked()
        return value

    def _evict_locked(self) -> None:
        while self._entries and (
            (self._budget is not None and self._bytes > self._budget)
            or (self._max_items is not None and len(self._entries) > self._max_items)
        ):
            if len(self._entries) == 1:
                break  # never evict the entry we just inserted
            _, entry = self._entries.popitem(last=False)
            self._bytes -= entry.size_bytes

    def drop_where(self, predicate: Callable[[Hashable], bool]) -> int:
        """Drop every entry whose KEY satisfies ``predicate``; returns
        the count. The residency manager (serving/residency.py) uses
        this to purge a released load-per-job model's executables —
        keyed by the dead components' ``id()``, they can never hit
        again and would otherwise thrash hot models out of the bounded
        executable LRU."""
        with self._lock:
            doomed = [k for k in self._entries if predicate(k)]
            for key in doomed:
                entry = self._entries.pop(key)
                self._bytes -= entry.size_bytes
        return len(doomed)

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def stats(self) -> dict[str, int]:
        return {
            "items": len(self._entries),
            "bytes": self._bytes,
            "hits": self.hits,
            "misses": self.misses,
        }


class CompileCache:
    """Process-wide residency for params and compiled pipelines.

    Since ISSUE 8, MODEL param residency is owned by the measured-ledger
    ``serving/residency.py::ResidencyManager`` (the registry routes every
    pipeline load through it); the byte-budgeted ``params`` LRU below
    remains for non-registry callers and API compatibility. Compiled
    executables stay here — they are per-process like before."""

    def __init__(self, param_budget_bytes: int = 24 * 1024**3,
                 max_executables: int = 16) -> None:
        self.params = LruCache(budget_bytes=param_budget_bytes,
                               kind="params")
        self.executables = LruCache(max_items=max_executables,
                                    kind="executables")

    def cached_params(self, key: Hashable, loader: Callable[[], Any],
                      size_bytes: int = 0,
                      size_of: Callable[[Any], int] | None = None) -> Any:
        return self.params.get_or_create(key, loader, size_bytes, size_of)

    def cached_executable(self, key: Hashable, builder: Callable[[], Any]) -> Any:
        # the first call of a fresh executable pays the lazy XLA compile;
        # _instrument_executable times exactly that call into /metrics
        return self.executables.get_or_create(
            key, lambda: _instrument_executable(builder(), _key_tag(key)))

    def flush_executables(self) -> int:
        """Drop EVERY cached executable (the guard's cache-flush heal
        rung, serving/guard.py): a sick device can serve a corrupted
        compiled program, and recompiling fresh is the cheapest rung
        above a lane rebuild. Params stay resident — the corruption
        mode this rung targets is the executable, not the weights.
        Returns the number dropped; the next calls recompile (or reload
        from the persistent XLA cache)."""
        return self.executables.drop_where(lambda _key: True)


GLOBAL_CACHE = CompileCache()
