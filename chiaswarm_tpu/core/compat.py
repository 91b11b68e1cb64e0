"""JAX crossing point — the one module that imports version-sensitive jax API.

The repo runs on ONE installation: jax/jaxlib 0.9.0 (``pyproject.toml``
floors jax there; sandbox and chip machine ship the same wheels). No
branch in this file chooses between jax versions. What remains is the
reason the module exists at all: JAX moves public surface between minors
(``shard_map`` left ``jax.experimental``, ``io_callback`` has not left it
yet, ``jax.experimental.*`` promises nothing), so every symbol that has
moved — or still lives under ``jax.experimental`` — is imported HERE and
nowhere else. The next bump then edits one file.

- ``COMPAT_TABLE`` is pure data (no jax import needed to read it) and
  drives the ``compat-import`` lint rule (R3) in
  ``chiaswarm_tpu.analysis`` — any module outside this file that imports
  a listed symbol directly is a finding.
- The shims resolve lazily via module ``__getattr__`` so that importing
  this module (from the linter, or a host-only tool) never drags in the
  jax runtime.

Usage::

    from chiaswarm_tpu.core.compat import shard_map
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class CompatEntry:
    """One symbol that must be imported from this module."""

    symbol: str           # name exported by this module
    path: str             # where it lives on the installed jax (0.9.0)
    note: str = ""


#: Symbols that MUST be imported from this module rather than from jax
#: directly. Keys are ``"<module>:<name>"`` import forms that the
#: ``compat-import`` rule rejects anywhere outside this file.
COMPAT_TABLE: dict[str, CompatEntry] = {
    "jax:shard_map": CompatEntry(
        symbol="shard_map",
        path="jax.shard_map",
        note="moved out of jax.experimental in 0.6; one spelling, here",
    ),
    "jax.experimental.shard_map:shard_map": CompatEntry(
        symbol="shard_map",
        path="jax.shard_map",
        note="deprecated alias (warns since 0.8, slated for removal)",
    ),
    "jax.lax:axis_size": CompatEntry(
        symbol="axis_size",
        path="jax.lax.axis_size",
        note="young API (absent before 0.5); kept behind one name",
    ),
    # jax.profiler is stable, but serving code must still cross here:
    # the shims degrade to no-ops when the profiler plugin (or jax
    # itself) is absent, so stdlib-only observability callers
    # (chiaswarm_tpu/obs) never crash a job because tracing is broken
    "jax.profiler:trace": CompatEntry(
        symbol="profiler_trace",
        path="jax.profiler.trace",
        note="route through compat.profiler_trace: degrades to a no-op "
             "context manager when the profiler backend is unavailable",
    ),
    "jax.profiler:TraceAnnotation": CompatEntry(
        symbol="trace_annotation",
        path="jax.profiler.TraceAnnotation",
        note="route through compat.trace_annotation: degrades to a no-op "
             "when the profiler backend is unavailable",
    ),
    "jax.profiler:start_trace": CompatEntry(
        symbol="profiler_start_trace",
        path="jax.profiler.start_trace",
        note="route through compat.profiler_start_trace (no-op fallback)",
    ),
    "jax.profiler:stop_trace": CompatEntry(
        symbol="profiler_stop_trace",
        path="jax.profiler.stop_trace",
        note="route through compat.profiler_stop_trace (no-op fallback)",
    ),
    # the swarmlens numerics-tap emission primitive (obs/numerics.py)
    # still lives under jax.experimental on 0.9.0
    "jax.experimental:io_callback": CompatEntry(
        symbol="io_callback",
        path="jax.experimental.io_callback",
        note="experimental namespace: one sanctioned import site",
    ),
}

#: ``jax.experimental`` submodules that modules may import at module scope
#: without a try/except guard. Everything else under ``jax.experimental``
#: must be guarded or shimmed here — the ``compat-import`` rule enforces
#: it. Pallas is allowed because the kernels (ops/flash_attention.py,
#: ops/ring_flash_attention.py) ARE Pallas programs: they import it at
#: module top and a broken import must raise, not hide.
ALLOWED_EXPERIMENTAL: frozenset[str] = frozenset({
    "jax.experimental.pallas",
})


def _resolve_shard_map():
    import jax

    return jax.shard_map


def _resolve_axis_size():
    import jax

    return jax.lax.axis_size


class _NoopAnnotation:
    """Stand-in for jax.profiler.TraceAnnotation when the profiler (or
    jax itself) is unavailable — observability must never fail a job."""

    def __init__(self, *_args, **_kwargs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


def _resolve_trace_annotation():
    try:
        import jax

        return jax.profiler.TraceAnnotation
    except Exception:
        return _NoopAnnotation


def _resolve_profiler_trace():
    try:
        import jax

        return jax.profiler.trace
    except Exception:
        return _NoopAnnotation  # same no-op context-manager shape


def _resolve_profiler_start_trace():
    try:
        import jax

        return jax.profiler.start_trace
    except Exception:
        return lambda *a, **k: None


def _resolve_profiler_stop_trace():
    try:
        import jax

        return jax.profiler.stop_trace
    except Exception:
        return lambda *a, **k: None


def _resolve_io_callback():
    from jax.experimental import io_callback

    return io_callback


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with the varying-manual-axes check off — required
    for bodies containing ``pallas_call`` (no replication rule exists
    for it; the fused ring-flash kernel and its interpret oracle both
    hit this)."""
    return __getattr__("shard_map")(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)


# ---------------------------------------------------------------------------
# hardware capability probes (ISSUE 18): not moved-symbol shims, but the
# same "one place that knows" stance — convert/quantize.py's activation
# seam asks HERE whether fp8 is usable rather than sniffing device kinds
# itself. Plain functions (not lazy attrs) so callers get a stable
# signature to mock in tests.

#: TPU generations WITHOUT native fp8 matmul support. v5p/v6e and later
#: accept float8_e4m3fn operands; older chips would silently upcast (or
#: fail to lower), so the activation seam falls back to int8 there.
_FP8_LESS_TPUS = ("v2", "v3", "v4", "v5 lite", "v5e")


def float8_dtype():
    """The fp8 activation dtype (e4m3: the forward-pass variant — more
    mantissa, the weights/activations choice in every mixed-fp8 recipe),
    or None when this jax build does not ship float8 dtypes."""
    try:
        import jax.numpy as jnp

        return jnp.float8_e4m3fn
    except Exception:
        return None


def fp8_supported() -> bool:
    """True when fp8 activations can run on the CURRENT backend: the
    dtype exists AND the accelerator has fp8 matmul units. Non-TPU
    backends (the hermetic CPU tier) count as supported when the dtype
    exists — XLA emulates the conversions, which is exactly what the
    parity tests need; the generation gate only bites on real TPUs."""
    if float8_dtype() is None:
        return False
    try:
        import jax

        if jax.default_backend() != "tpu":
            return True
        kind = jax.devices()[0].device_kind.lower()
        return not any(kind.startswith(old) or old in kind
                       for old in _FP8_LESS_TPUS)
    except Exception:
        return False


_LAZY = {
    "shard_map": _resolve_shard_map,
    "axis_size": _resolve_axis_size,
    "trace_annotation": _resolve_trace_annotation,
    "profiler_trace": _resolve_profiler_trace,
    "profiler_start_trace": _resolve_profiler_start_trace,
    "profiler_stop_trace": _resolve_profiler_stop_trace,
    "io_callback": _resolve_io_callback,
}
_cache: dict[str, object] = {}


def __getattr__(name: str):
    if name in _LAZY:
        if name not in _cache:
            _cache[name] = _LAZY[name]()
        return _cache[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
