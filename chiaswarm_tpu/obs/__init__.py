"""swarmscope + swarmlens — the unified observability subsystem.

Five layers, one vocabulary (ISSUE 4 + ISSUE 11):

- ``metrics``   — Prometheus-style :class:`Registry` of counters /
                  gauges / histograms; ``/metrics`` exposition and the
                  ``/healthz`` read-through view.
- ``trace``     — Dapper-style per-job span trees on ``perf_counter``
                  (poll -> execute -> encode/step/decode -> upload),
                  kept in a bounded ring and exported as
                  Perfetto-loadable JSON at ``/debug/traces``; every
                  span is also a ``TraceAnnotation`` ``swarm.<name>``
                  on the profiler's clock (one primitive, two clocks).
- ``profiling`` — ``jax.profiler`` behind ``core/compat.py``:
                  on-demand XLA captures (``/debug/profile``,
                  ``CHIASWARM_PROFILE_DIR``).
- ``numerics``  — the swarmlens flight recorder (ISSUE 11): named
                  probes compiled INTO jitted programs behind
                  ``CHIASWARM_NUMERICS`` (env off = identity at trace
                  time), per-step per-shard summaries in a bounded
                  ring at ``/debug/numerics``, and the stream format
                  ``tools/divergence_bisect.py`` aligns.
- ``hlocost``   — the static HLO parser (the walker
                  ``analysis/hlocheck.py`` shares; conv/dot/flash FLOPs
                  and HBM bytes per instruction) and ``ProgramCapture``
                  for the audit tools and ``chip_smoke.py``; no clock
                  and no peaks (those are the benchmark's).

Like ``analysis/``, this package imports without jax, aiohttp, or any
accelerator — host tools, the linter environment, and CI jobs can load
it anywhere. Instrumentation is always-on and allocation-light;
profiler capture and numerics taps are opt-in.
"""

from chiaswarm_tpu.obs.metrics import (  # noqa: F401
    CONTENT_TYPE,
    DEFAULT_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    render_all,
)
from chiaswarm_tpu.obs.trace import (  # noqa: F401
    TRACE_KEY,
    TRACE_RING,
    JobTrace,
    Span,
    TraceRing,
    activate,
    attach,
    current_span,
    detach,
    job_trace,
    span,
)
from chiaswarm_tpu.obs.profiling import (  # noqa: F401
    PROFILE_DIR_ENV,
    capture,
    job_profile,
    profiler_available,
)
from chiaswarm_tpu.obs.numerics import (  # noqa: F401
    RING,
    TAPS,
    NumericsRing,
    TapRegistry,
    numerics_enabled,
    tap,
)
