"""The one traffic generator. A mix is a data file, ``traffic/<name>.json``.

Keys of a mix:

- ``loop``: ``"closed"``: ``clients`` callers, each sends its next job
  when its last one settled (one client is one person waiting; many
  more than the worker holds in flight is a backlog). An open loop
  (arrivals on a schedule) comes with the benchmark PR that proves its
  first cell on the chip;
- ``steps``: ``[[num_inference_steps, share], ...]``, shares exact over
  each block of ``steps_block`` jobs (default 10), order from the seed;
- ``warm_solo`` / ``warm_burst``: the warm-up the mix's shapes need, as
  ``[[num_inference_steps, count], ...]``: solo jobs run one after the
  other, the burst is submitted at once (so lanes grow to the widths
  the window will use: the worker sizes a lane by how many jobs one
  poll brought, so the burst is as large as the window's first poll).
  Warm-up is set-up, not traffic.

Every seed gets the SAME multiset of step counts, in another order, plus
its own prompts and noise seeds: the seed must not change the amount of
work, only its arrangement.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: prompts are lower-case a-z words (the reference's tokenizer contract)
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


def step_counts(mix: dict, n: int, seed: int) -> list[int]:
    """``n`` step counts in exact shares per block, shuffled per block."""
    block = int(mix.get("steps_block", 10))
    shares = [(int(s), float(w)) for s, w in mix["steps"]]
    base: list[int] = []
    for steps, share in shares:
        base += [steps] * round(share * block)
    if len(base) != block:
        raise ValueError(f"shares {shares} do not fill a block of {block}")
    rng = _rng(seed, "steps")
    out: list[int] = []
    while len(out) < n:
        chunk = list(base)
        rng.shuffle(chunk)
        out += chunk
    return out[:n]


def make_job(index: int, steps: int, seed: int, config: dict,
             model_name: str, tag: str = "w") -> dict:
    """One hive job. Prompt words and the noise seed come from
    (``--seed``, index) alone."""
    rng = _rng(seed, f"job:{tag}:{index}")
    serving = config["serving"]
    return {
        "id": f"{tag}{index:05d}",
        "model_name": model_name,
        "workflow": serving["workflow"],
        "prompt": " ".join(rng.choice(WORDS) for _ in range(8)),
        "seed": rng.randrange(2 ** 31),
        "num_inference_steps": int(steps),
        "guidance_scale": float(serving["guidance_scale"]),
        "height": int(serving["height"]),
        "width": int(serving["width"]),
        "content_type": serving["content_type"],
    }


def warm_jobs(mix: dict, seed: int, config: dict, model_name: str):
    """(solo jobs, burst jobs) of the mix's warm-up."""
    def expand(key, tag):
        jobs, i = [], 0
        for steps, count in mix.get(key, []):
            for _ in range(int(count)):
                jobs.append(make_job(i, int(steps), seed, config,
                                     model_name, tag=tag))
                i += 1
        return jobs

    return expand("warm_solo", "ws"), expand("warm_burst", "wb")
