"""The text stacks ``pipelines/text.py`` serves, by name.

A stack is a module of plain functions over a dict pytree of weights and
its own frozen configuration: ``TINY`` (the CPU tests' preset),
``param_shapes`` / ``random_params`` (the checkpoint layout and a random
fill of it), ``empty_prefill_caches`` / ``prefill_chunk`` (one chunk of
one row through every layer's cache), ``decode_caches`` / ``decode_step``
(one new token a row), ``cache_bytes`` and ``job_counts`` (what the host
knows of a job, for the counters). A configuration names its stack
(``stack``, a class attribute), so whoever holds the configuration can
find the functions that read it; a catalog entry names it (``"stack"``)
where there is no configuration yet.
"""

from __future__ import annotations

import importlib

NAMES = ("ling", "deepseek", "laguna")
DEFAULT = "ling"


def get(name: str):
    if name not in NAMES:
        raise ValueError(f"unknown text stack {name!r}; known: {NAMES}")
    return importlib.import_module(f"chiaswarm_tpu.models.{name}")
