"""The one traffic generator. A mix is a data file, ``traffic/<name>.json``.

Keys of a mix:

- ``loop``: ``"closed"``: ``clients`` callers, each sends its next job
  when its last one settled (one client is one person waiting; many
  more than the worker holds in flight is a backlog). An open loop
  (arrivals on a schedule) comes with the benchmark PR that proves its
  first cell on the chip;
- the kind's unit of work (``kinds/<kind>.py::UNIT``: ``steps`` where a
  job is so many denoising steps; prompt and output token counts for a
  text kind), as ``[[unit, share], ...]``, shares exact over each block
  of ``steps_block`` jobs (default 10), order from the seed;
- ``warm_solo`` / ``warm_burst``: the warm-up the mix's shapes need, as
  ``[[unit, count], ...]``: solo jobs run one after the
  other, the burst is submitted at once (so lanes grow to the widths
  the window will use: the worker sizes a lane by how many jobs one
  poll brought, so the burst is as large as the window's first poll).
  Warm-up is set-up, not traffic.

Every seed gets the SAME multiset of units, in another order, plus its
own job fields (the kind draws them from the job's RNG stream): the seed
must not change the amount of work, only its arrangement.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: lower-case a-z words a kind may draw its prompts from
WORDS = (
    "amber harbor dusk lantern river stone garden violet mountain fog "
    "copper tower meadow winter glass orchard silver bridge ember forest "
    "marble canyon velvet morning tide willow crimson desert paper moon "
    "cobalt village thunder field ivory lighthouse autumn rain golden "
    "market quiet island scarlet train misty valley bronze cathedral "
    "summer storm jade temple distant city woven sky"
).split()


def load_mix(name: str) -> dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    if mix.get("loop") != "closed":
        raise ValueError(f"traffic {name!r}: loop must be closed")
    return mix


def _rng(seed: int, stream: str) -> random.Random:
    return random.Random(f"{int(seed)}:{stream}")


def units(mix: dict, key: str, n: int, seed: int) -> list:
    """``n`` units of ``mix[key]`` in exact shares per block, shuffled
    per block (RNG stream ``key``)."""
    block = int(mix.get("steps_block", 10))
    base: list = []
    for unit, share in mix[key]:
        base += [unit] * round(float(share) * block)
    if len(base) != block:
        raise ValueError(f"shares {mix[key]} do not fill a block of {block}")
    rng = _rng(seed, key)
    out: list = []
    while len(out) < n:
        chunk = list(base)
        rng.shuffle(chunk)
        out += chunk
    return out[:n]


def make_job(kind, index: int, unit, seed: int, config: dict,
             model_name: str, tag: str = "w") -> dict:
    """One hive job of the kind. Whatever it draws comes from the RNG
    stream of (``--seed``, tag, index) alone."""
    return kind.job(_rng(seed, f"job:{tag}:{index}"), f"{tag}{index:05d}",
                    unit, config, model_name)


def unit_label(unit) -> str:
    """``30`` -> ``"30"``, ``[512, 64]`` -> ``"512_64"``."""
    parts = unit if isinstance(unit, (list, tuple)) else [unit]
    return "_".join(str(part) for part in parts)


def warm_jobs(kind, mix: dict, seed: int, config: dict, model_name: str):
    """(solo, burst) of the mix's warm-up, each a list of (unit, job)."""
    def expand(key, tag):
        jobs = []
        for unit, count in mix.get(key, []):
            for _ in range(int(count)):
                jobs.append((unit, make_job(kind, len(jobs), unit, seed,
                                            config, model_name, tag=tag)))
        return jobs

    return expand("warm_solo", "ws"), expand("warm_burst", "wb")
