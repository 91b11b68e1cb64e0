"""TPU profiler hooks: XLA traces on demand.

The metrics registry says *how often* and the span tracer says *where
in the worker* — this module answers *what the chip did*: it wraps
``jax.profiler`` (routed through ``core/compat.py`` so everything
degrades to a no-op when jax or the profiler plugin is absent) into

- :func:`capture` — a one-shot, duration-bounded trace capture backing
  the worker's ``/debug/profile?seconds=N`` endpoint (node/worker.py);
  output lands under the directory named by :data:`PROFILE_DIR_ENV`
  (or an explicit ``?dir=``/``out=``);
- :func:`job_profile` — the per-job opt-in trace the executor runs
  when :data:`PROFILE_DIR_ENV` is set.

The names a capture's host plane reads in (``swarm.lane.step``,
``swarm.png``, ...) are not made here: every ``obs.trace`` span is also
a ``TraceAnnotation`` (one primitive on both clocks, obs/trace.py).

The profiler is a process-global singleton, so one :data:`_CAPTURE_LOCK`
serializes all of the above: a busy profiler yields an explicit
"busy" result (or an unprofiled job), never a crashed job.

This module is importable without jax (stdlib + lazy compat), like the
rest of ``chiaswarm_tpu/obs``.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Any, Iterator

log = logging.getLogger("chiaswarm.obs.profiling")

#: directory on-demand captures (and the executor's per-job traces)
#: write under; ``/debug/profile`` falls back to it when the request
#: names no explicit directory
PROFILE_DIR_ENV = "CHIASWARM_PROFILE_DIR"

#: ceiling for /debug/profile?seconds=N — a forgotten capture must not
#: trace (and slow) the worker forever
MAX_CAPTURE_S = 120.0

_CAPTURE_LOCK = threading.Lock()


def profiler_available() -> bool:
    """Can this process record an XLA trace at all?"""
    try:
        import jax

        return hasattr(jax, "profiler")
    except Exception:
        return False


def default_profile_dir() -> str:
    return os.environ.get(PROFILE_DIR_ENV, "").strip()


def capture(seconds: float, out: str | None = None) -> dict[str, Any]:
    """Record an XLA trace for ``seconds`` (blocking; run it from a
    thread — node/worker.py uses ``run_in_executor``).

    Returns ``{"status": "ok", "dir": path, "seconds": n}`` or an
    explicit error/busy dict; raises nothing: this backs an HTTP
    endpoint and its failure modes (busy profiler, no backend, bad
    dir) are expected operator-visible states, not crashes.
    """
    seconds = max(0.1, min(float(seconds), MAX_CAPTURE_S))
    out = out or default_profile_dir()
    if not out:
        return {"status": "error",
                "error": f"no capture directory: set {PROFILE_DIR_ENV} "
                         f"or pass ?dir="}
    if not profiler_available():
        return {"status": "error",
                "error": "jax.profiler is unavailable in this process"}
    if not _CAPTURE_LOCK.acquire(blocking=False):
        return {"status": "busy",
                "error": "another profiler capture is already running "
                         "(the profiler is process-global)"}
    try:
        from chiaswarm_tpu.core import compat

        target = os.path.join(
            out, time.strftime("capture-%Y%m%d-%H%M%S"))
        os.makedirs(target, exist_ok=True)
        compat.profiler_start_trace(target)
        try:
            time.sleep(seconds)
        finally:
            compat.profiler_stop_trace()
        log.info("profiler capture (%.1fs) written to %s", seconds, target)
        return {"status": "ok", "dir": target, "seconds": seconds}
    except Exception as exc:
        log.warning("profiler capture failed: %s", exc)
        return {"status": "error", "error": f"{type(exc).__name__}: {exc}"}
    finally:
        _CAPTURE_LOCK.release()


@contextlib.contextmanager
def job_profile(job_id: Any,
                profile_dir: str | None = None) -> Iterator[bool]:
    """Per-job XLA trace when :data:`PROFILE_DIR_ENV` is set — the
    executor's opt-in hook (node/executor.py). Yields True when a trace
    is actually recording. Shares :data:`_CAPTURE_LOCK` with
    :func:`capture`: overlapping jobs (multi-slot workers) and
    on-demand captures skip rather than fight over the process-global
    profiler."""
    profile_dir = (default_profile_dir() if profile_dir is None
                   else profile_dir)
    if not profile_dir:
        yield False
        return
    if not _CAPTURE_LOCK.acquire(blocking=False):
        log.info("job %s not profiled: profiler busy", job_id)
        yield False
        return
    try:
        target = os.path.join(profile_dir, str(job_id or "job"))
        try:
            from chiaswarm_tpu.core import compat

            cm = compat.profiler_trace(target)
            cm.__enter__()
        except Exception as exc:
            log.warning("job %s profile failed to start (%s); job "
                        "continues unprofiled", job_id, exc)
            yield False
            return
        try:
            yield True
        finally:
            try:
                cm.__exit__(None, None, None)
                log.info("job %s profile written to %s", job_id, target)
            except Exception as exc:
                log.warning("job %s profile failed to finalize (%s)",
                            job_id, exc)
    finally:
        _CAPTURE_LOCK.release()
