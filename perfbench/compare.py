"""What decides ``correct``: the uploaded pixels against the plain
reference, job by job.

After the window has closed and the worker is gone, a sample of the
window's finished jobs - the one with the most steps (of several, the
one settled last, which went into a lane that had served others), the
one settled first, then others drawn from the seed - is recomputed by
``perfbench/reference.py`` from the job's own prompt, seed and step
count, in float32, and the PNG the hive received is held against it.
One number a job:

    image_gap = || uploaded - reference ||_2 / || reference - mean ||_2

over all pixels and channels (the reference left unrounded in [0, 255]).
It covers the text encoders, every lane step (UNet with the flash
kernels, CFG, the sampler update) and the VAE decode up to the PNG. The
run's number is the worst job's; its limit is in the configuration's
file (``compare.image_gap_limit``) with the readings it was set from in
PERF.md.

``control`` is the comparison that has to fail: the reference itself in
the nearest precision below the one the configuration states, put in the
program's place on the window's own jobs and judged by ``check`` like
any run (``run.py --control N``).
"""

from __future__ import annotations

import random


#: the nearest precision below the one a configuration states
CONTROL_OF = {"float32": "bfloat16", "bfloat16": "fp8"}


def pick(good: list[dict], sent: dict, seed: int, n_jobs: int) -> list[dict]:
    """The longest job (of several, the last settled), the first settled,
    then more from the seed: ``n_jobs`` in all, none twice."""
    if not good or n_jobs < 1:
        return []
    ordered = sorted(good, key=lambda s: (s["t"], s["id"]))
    longest = max(reversed(ordered),
                  key=lambda s: sent[s["id"]]["job"]["num_inference_steps"])
    rest = [s for s in ordered[1:] if s is not longest]
    random.Random(f"{int(seed)}:compare").shuffle(rest)
    chosen = [longest] + [s for s in ordered[:1] if s is not longest] + rest
    return chosen[:n_jobs]


def image_gap(uploaded, reference_pixels) -> float:
    import numpy as np

    got = np.asarray(uploaded, np.float64)
    want = np.asarray(reference_pixels, np.float64)
    if got.shape != want.shape:
        return float("inf")
    spread = np.linalg.norm(want - want.mean())
    return float(np.linalg.norm(got - want) / max(spread, 1e-9))


def check(params, config: dict, good: list[dict], sent: dict, *,
          seed: int, n_jobs: int | None, decode) -> dict:
    """-> {"ok", "numbers": {name: {"value", "limit"}}, "jobs": [...]}"""
    from perfbench import reference

    spec = config["compare"]
    n_jobs = int(spec["jobs"] if n_jobs is None else n_jobs)
    limit = float(spec["image_gap_limit"])
    serving = config["serving"]
    gaps, rows = [], []
    for item in pick(good, sent, seed, n_jobs):
        job = sent[item["id"]]["job"]
        want = reference.generate(
            params, config, prompt=job["prompt"], seed=job["seed"],
            steps=job["num_inference_steps"],
            guidance=job["guidance_scale"], height=serving["height"],
            width=serving["width"])
        gap = image_gap(decode(item["result"]), want)
        gaps.append(gap)
        rows.append({"id": item["id"],
                     "steps": job["num_inference_steps"], "gap": gap})
    worst = max(gaps) if gaps else float("inf")
    return {"ok": worst <= limit, "jobs": rows,
            "numbers": {"image_gap": {"value": worst, "limit": limit}}}


def control(params, config: dict, jobs: list[dict], *, seed: int) -> dict:
    """``check`` over ``jobs`` as if the lower-precision reference had
    served them: its pixels rounded to the bytes a PNG holds. The
    verdict's ``precision`` names the control."""
    import numpy as np

    from perfbench import reference

    precision = CONTROL_OF[config["serving"]["dtype"]]
    serving = config["serving"]
    good, sent = [], {}
    for order, job in enumerate(jobs):
        pixels = reference.generate(
            params, config, precision=precision, prompt=job["prompt"],
            seed=job["seed"], steps=job["num_inference_steps"],
            guidance=job["guidance_scale"], height=serving["height"],
            width=serving["width"])
        good.append({"id": job["id"], "t": float(order),
                     "result": np.clip(np.round(pixels), 0, 255
                                       ).astype(np.uint8)})
        sent[job["id"]] = {"job": job}
    verdict = check(params, config, good, sent, seed=seed,
                    n_jobs=len(jobs), decode=lambda pixels: pixels)
    verdict["precision"] = precision
    return verdict
