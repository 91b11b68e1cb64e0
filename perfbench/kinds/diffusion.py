"""The diffusion kind: txt2img jobs through the stepper's lanes, a PNG
back. Owns ``perfbench/reference.py`` (the plain float32 pipeline) and
``perfbench/flops.py`` (what a job and an attention site cost).

What decides ``correct``: the uploaded pixels against the plain
reference, job by job. After the window has closed and the worker is
gone, a sample of the window's finished jobs (``compare.pick``: the one
with the most steps, the one settled first, then others drawn from the
seed) is recomputed by ``reference.generate`` from the job's own prompt,
seed and step count, in float32, and the PNG the hive received is held
against it. One number a job:

    image_gap = || uploaded - reference ||_2 / || reference - mean ||_2

over all pixels and channels (the reference left unrounded in [0, 255]).
It covers the text encoders, every lane step (UNet with the flash
kernels, CFG, the sampler update) and the VAE decode up to the PNG. The
run's number is the worst job's; its limit is in the configuration's
file (``compare.image_gap_limit``) with the readings it was set from in
PERF.md.
"""

from __future__ import annotations

import base64
import io

from perfbench import compare
from perfbench.traffic import WORDS

UNIT = "steps"
PROGRAM_MODULES = ("chiaswarm_tpu.pipelines.diffusion",)


# ---- weights and registry ------------------------------------------------


def seeded_params(config: dict, seed: int, device):
    from chiaswarm_tpu.models.configs import FAMILIES
    from chiaswarm_tpu.pipelines.components import abstract_params

    from perfbench.weights import make_params

    return make_params(
        abstract_params(FAMILIES[config["program_family"]]), seed,
        dtype=config["serving"]["dtype"], device=device)


def build_components(config: dict, seed: int, device):
    """The program's ``Components`` around weights made here from the
    seed (``perfbench/weights.py``); returns (components, params)."""
    from chiaswarm_tpu.models.clip import ClipTextEncoder
    from chiaswarm_tpu.models.configs import FAMILIES
    from chiaswarm_tpu.models.tokenizer import HashTokenizer
    from chiaswarm_tpu.models.unet import UNet
    from chiaswarm_tpu.models.vae import AutoencoderKL
    from chiaswarm_tpu.pipelines.components import Components

    family = FAMILIES[config["program_family"]]
    params = seeded_params(config, seed, device)
    components = Components(
        family=family, model_name=f"bench/{config['name']}",
        tokenizers=[HashTokenizer(cfg.vocab_size,
                                  cfg.max_position_embeddings,
                                  cfg.eos_token_id)
                    for cfg in family.text_encoders],
        text_encoders=[ClipTextEncoder(cfg)
                       for cfg in family.text_encoders],
        unet=UNet(family.unet), vae=AutoencoderKL(family.vae),
        params=params)
    return components, params


def build(config: dict, seed: int, device):
    """A ``ModelRegistry`` whose checkpoint loader hands out the
    benchmark's seeded weights; everything after the load (quantize
    hook, placement, pipeline, residency ledger) is the program's own."""
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.serving.residency import ResidencyManager

    components, params = build_components(config, seed, device)

    class SeededRegistry(ModelRegistry):
        def _load_components(self, model_name):
            return components

    registry = SeededRegistry(
        catalog=[{"name": components.model_name,
                  "family": config["program_family"]}],
        residency=ResidencyManager())
    return registry, params, components.model_name


# ---- jobs ----------------------------------------------------------------


def job(rng, job_id: str, unit, config: dict, model_name: str) -> dict:
    """Prompt words (lower-case a-z: the reference's tokenizer contract)
    and the noise seed come from ``rng``; the unit is the step count."""
    serving = config["serving"]
    return {
        "id": job_id,
        "model_name": model_name,
        "workflow": serving["workflow"],
        "prompt": " ".join(rng.choice(WORDS) for _ in range(8)),
        "seed": rng.randrange(2 ** 31),
        "num_inference_steps": int(unit),
        "guidance_scale": float(serving["guidance_scale"]),
        "height": int(serving["height"]),
        "width": int(serving["width"]),
        "content_type": serving["content_type"],
    }


def job_size(job: dict) -> int:
    return job["num_inference_steps"]


# ---- comparison ----------------------------------------------------------


def decode_artifact(result: dict):
    """The uploaded PNG as uint8 pixels (H, W, 3)."""
    import numpy as np
    from PIL import Image

    blob = base64.b64decode(result["artifacts"]["primary"]["blob"])
    return np.asarray(Image.open(io.BytesIO(blob)).convert("RGB"))


def image_gap(uploaded, reference_pixels) -> float:
    import numpy as np

    got = np.asarray(uploaded, np.float64)
    want = np.asarray(reference_pixels, np.float64)
    if got.shape != want.shape:
        return float("inf")
    spread = np.linalg.norm(want - want.mean())
    return float(np.linalg.norm(got - want) / max(spread, 1e-9))


def _reference_pixels(params, config: dict, job: dict, **kw):
    from perfbench import reference

    serving = config["serving"]
    return reference.generate(
        params, config, prompt=job["prompt"], seed=job["seed"],
        steps=job["num_inference_steps"], guidance=job["guidance_scale"],
        height=serving["height"], width=serving["width"], **kw)


def check(params, config: dict, good: list[dict], sent: dict, *,
          seed: int, n_jobs: int | None, decode=decode_artifact) -> dict:
    spec = config["compare"]
    n_jobs = int(spec["jobs"] if n_jobs is None else n_jobs)
    limit = float(spec["image_gap_limit"])
    gaps, rows = [], []
    for item in compare.pick(good, sent, seed, n_jobs, job_size):
        job = sent[item["id"]]["job"]
        want = _reference_pixels(params, config, job)
        gap = image_gap(decode(item["result"]), want)
        gaps.append(gap)
        rows.append({"id": item["id"],
                     "steps": job["num_inference_steps"], "gap": gap})
    worst = max(gaps) if gaps else float("inf")
    return {"ok": worst <= limit, "jobs": rows,
            "numbers": {"image_gap": {"value": worst, "limit": limit}}}


def control(params, config: dict, jobs: list[dict], *, seed: int) -> dict:
    """``check`` over ``jobs`` as if the lower-precision reference had
    served them: its pixels rounded to the bytes a PNG holds."""
    import numpy as np

    precision = compare.CONTROL_OF[config["serving"]["dtype"]]
    good, sent = [], {}
    for order, job in enumerate(jobs):
        pixels = _reference_pixels(params, config, job,
                                   precision=precision)
        good.append({"id": job["id"], "t": float(order),
                     "result": np.clip(np.round(pixels), 0, 255
                                       ).astype(np.uint8)})
        sent[job["id"]] = {"job": job}
    verdict = check(params, config, good, sent, seed=seed,
                    n_jobs=len(jobs), decode=lambda pixels: pixels)
    verdict["precision"] = precision
    return verdict


# ---- the work of a job ---------------------------------------------------


def job_flops(config: dict, job: dict) -> float:
    from perfbench import flops

    serving = config["serving"]
    return flops.job(config, job["num_inference_steps"],
                     serving["height"], serving["width"])


def kernel_sites(config: dict) -> list[tuple]:
    """Flash calls at the attention sizes the configuration states."""
    from perfbench import flops

    serving = config["serving"]
    return flops.attention_sites(config, serving["height"],
                                 serving["width"])


# ---- its own file rules --------------------------------------------------


def check_config(config: dict) -> None:
    for group in ("unet", "text_encoders", "vae", "scheduler"):
        assert group in config, group
    assert 0 < config["compare"]["image_gap_limit"] < 1
    # nothing read off the program's internals sits in the file
    assert set(config["serving"]) == {"height", "width", "guidance_scale",
                                      "dtype", "content_type", "workflow"}


def check_mix(mix: dict) -> None:
    counts = {int(steps) for steps, _ in mix["steps"]}
    assert all(steps >= 1 for steps in counts)
    # every step count of the window is warmed solo (its ladder's
    # one-off host programs compile per count)
    assert counts <= {int(steps) for steps, _ in mix["warm_solo"]}
