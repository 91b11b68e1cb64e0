"""What every kind's comparison shares: which of the window's jobs are
recomputed, and which precision the control drops to.

After the window has closed and the worker is gone, a sample of the
window's finished jobs - the longest by the kind's own measure (of
several, the one settled last, which went into a path that had served
others), the one settled first, then others drawn from the seed - is
recomputed by the kind's plain float32 reference and held against the
artifact the hive received (``kinds/<kind>.py::check``).

The kind's ``control`` is the comparison that has to fail: the reference
itself in the nearest precision below the one the configuration states
(``CONTROL_OF``), put in the program's place on the window's own jobs
and judged by ``check`` like any run (``run.py --control N``).
"""

from __future__ import annotations

import random


#: the nearest precision below the one a configuration states
CONTROL_OF = {"float32": "bfloat16", "bfloat16": "fp8"}


def pick(good: list[dict], sent: dict, seed: int, n_jobs: int,
         size) -> list[dict]:
    """The longest job by ``size(job)`` (of several, the last settled),
    the first settled, then more from the seed: ``n_jobs`` in all, none
    twice."""
    if not good or n_jobs < 1:
        return []
    ordered = sorted(good, key=lambda s: (s["t"], s["id"]))
    longest = max(reversed(ordered),
                  key=lambda s: size(sent[s["id"]]["job"]))
    rest = [s for s in ordered[1:] if s is not longest]
    random.Random(f"{int(seed)}:compare").shuffle(rest)
    chosen = [longest] + [s for s in ordered[:1] if s is not longest] + rest
    return chosen[:n_jobs]
