"""Device time by jitted program, from the profiler trace.

The chip's plane carries one line, "XLA Modules", with one event for each
execution of a compiled program, named ``<module>(<fingerprint>)``; the
module of a jitted function ``f`` is ``jit_f``. Since ISSUE 26 the
program's lane executables are named for their cache tags
(``jit_stepper_step``, ``jit_stepper_decode``, ...), so device time splits
by program. The reduction works on a plain form, like
``perfbench/trace.py``'s:

    {"window_s": float, "modules": [[name, start_ns, dur_ns], ...]}
"""

from __future__ import annotations

import glob

LINE = "XLA Modules"


def load(directory: str, window_s: float) -> dict:
    """The plain form of the newest ``.xplane.pb`` under ``directory``
    (first TPU plane, as ``trace.load`` takes it)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{directory}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane under {directory}")
    data = ProfileData.from_file(paths[-1])
    planes = sorted((p for p in data.planes
                     if p.name.startswith("/device:TPU")),
                    key=lambda p: p.name)
    modules: list[list] = []
    for line in (planes[0].lines if planes else ()):
        if line.name != LINE:
            continue
        for event in line.events:
            modules.append([event.name[:200], int(event.start_ns),
                            int(event.duration_ns)])
    return {"window_s": float(window_s), "modules": modules}


def program_name(event_name: str) -> str:
    """``jit_stepper_step(4316745985256595699)`` -> ``jit_stepper_step``."""
    return event_name.split("(")[0].strip()
