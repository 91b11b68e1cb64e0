"""What the harness asks of a kind of job, found by the name a
configuration's file gives under ``kind`` (``kinds/<kind>.py``), as a
per-layer metric's reader is found by its metric file.

``cell.py`` keeps what is common to every job the worker serves: the
look for a chip, the compile cache, ``MiniHive`` + ``Worker`` + a
one-slot ``ChipPool``, warm-up then window on the hive's clock, the
closed loop, the compile counter, peak memory, the drain, the result
line. ``traffic.py`` keeps the loop, the clients, the block-exact shares
and the per-stream RNGs; ``compare.py`` the sample and the table of
controls. Everything that knows what a job of this kind is sits in the
kind's module, which holds:

``UNIT``
    the key of a mix whose ``[[unit, share], ...]`` the generator fills
    (the unit of work a share is over), also the name of its RNG stream.
``PROGRAM_MODULES``
    import paths of the program's modules whose ``toplevel_jit``
    ``hlo.ProgramCapture`` patches in a traced run.
``seeded_params(config, seed, device)``
    the seeded weights on the device, in the layout the program loads.
``build(config, seed, device) -> (registry, params, model_name)``
    those weights behind the ``ModelRegistry`` subclass that hands them
    to the program's own load path.
``job(rng, job_id, unit, config, model_name) -> dict``
    one hive job; everything random in it is drawn from ``rng``.
``job_size(job)``
    how long a job is, for the sample's "longest job".
``check(params, config, good, sent, *, seed, n_jobs)``
    the artifacts the hive received against the kind's plain float32
    reference -> ``{"ok", "numbers": {name: {"value", "limit"}}, "jobs"}``.
``control(params, config, jobs, *, seed)``
    the same verdict (plus ``precision``) with the reference one
    precision down in the program's place; it has to come out not ok.
``job_flops(config, job)`` / ``kernel_sites(config)``
    the operations one job needs (``step_mfu``) and the kernel sites a
    Mosaic call is priced at (the rooflines).
``check_config(config)`` / ``check_mix(mix)``
    what the kind's configuration and mix files must hold (asserts), for
    ``tests/bench/test_bench_files.py``.
"""

from __future__ import annotations

import importlib

#: what a kind's module holds (above)
NAMES = ("UNIT", "PROGRAM_MODULES", "seeded_params", "build", "job",
         "job_size", "check", "control", "job_flops", "kernel_sites",
         "check_config", "check_mix")


def of(config: dict):
    """The module of the configuration's kind, whole."""
    kind = importlib.import_module(f"perfbench.kinds.{config['kind']}")
    missing = [name for name in NAMES if not hasattr(kind, name)]
    if missing:
        raise AttributeError(f"kind {config['kind']!r} lacks {missing}")
    return kind
