"""Smoke-test harness: run one hard-coded job per workflow through the real
dispatch + execution stack, no hive required.

Capability parity with swarm/test.py:7-77 (the reference's only test path),
upgraded from "edit the source to pick a job" to a CLI:

    python -m chiaswarm_tpu.node.smoke --workflow txt2img
    python -m chiaswarm_tpu.node.smoke --all --random-weights

``--random-weights`` fabricates weights for missing checkpoints so the
harness runs on a fresh node (the reference requires real downloads).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

SMOKE_JOBS: dict[str, dict[str, Any]] = {
    "txt2img": {
        "id": "smoke-txt2img",
        "model_name": "tiny",
        "prompt": "a lighthouse on a cliff at golden hour",
        "num_inference_steps": 4,
        "height": 64, "width": 64,
        "content_type": "image/png",
    },
    "img2img": {
        "id": "smoke-img2img",
        "model_name": "tiny",
        "prompt": "watercolor style",
        "num_inference_steps": 4,
        "strength": 0.6,
        "content_type": "image/png",
        "_inject_image": True,  # filled below (no network in smoke)
    },
    "txt2audio": {
        "id": "smoke-txt2audio",
        "workflow": "txt2audio",
        "model_name": "random/tiny_audio",
        "prompt": "rain on a tin roof",
        "num_inference_steps": 2,
        "audio_length_in_s": 0.1,
        "content_type": "audio/wav",
    },
    "txt2vid": {
        "id": "smoke-txt2vid",
        "workflow": "txt2vid",
        "model_name": "random/tiny_vid",
        "prompt": "a paper boat drifting",
        "num_frames": 8,
        "num_inference_steps": 2,
        "content_type": "video/mp4",
    },
    "img2txt": {
        "id": "smoke-img2txt",
        "workflow": "img2txt",
        "model_name": "Salesforce/blip-image-captioning-base",
        "content_type": "application/json",
        "_inject_image": True,
    },
    "txt2txt": {
        # words of the tiny preset's vocabulary (pipelines/text.py)
        "id": "smoke-txt2txt",
        "workflow": "txt2txt",
        "model_name": "random/ling_tiny",
        "prompt": "ab cd ab ba",
        "max_new_tokens": 4,
        "num_return_sequences": 2,
        "logprobs": True,
        "content_type": "application/json",
    },
    "txt2txt_deepseek": {
        # the second text stack (models/deepseek.py), named by its
        # catalog entry in ``run_smoke``
        "id": "smoke-txt2txt-deepseek",
        "workflow": "txt2txt",
        "model_name": "random/deepseek_tiny",
        "prompt": "ab cd ab ba",
        "max_new_tokens": 4,
        "num_return_sequences": 2,
        "logprobs": True,
        "content_type": "application/json",
    },
    "txt2txt_laguna": {
        # the third text stack (models/laguna.py): window and full
        # attention over plain keys and values
        "id": "smoke-txt2txt-laguna",
        "workflow": "txt2txt",
        "model_name": "random/laguna_tiny",
        "prompt": "ab cd ab ba",
        "max_new_tokens": 4,
        "num_return_sequences": 2,
        "logprobs": True,
        "content_type": "application/json",
    },
    "txt2txt_solar": {
        # the fourth text stack (models/solar.py): delta-rule layers beside
        # softmax attention over plain keys and values
        "id": "smoke-txt2txt-solar",
        "workflow": "txt2txt",
        "model_name": "random/solar_tiny",
        "prompt": "ab cd ab ba",
        "max_new_tokens": 4,
        "num_return_sequences": 2,
        "logprobs": True,
        "content_type": "application/json",
    },
    "txt2txt_lfm2": {
        # the fifth text stack (models/lfm2.py): gated short convs beside
        # QK-normed rotary attention, experts with no shared one
        "id": "smoke-txt2txt-lfm2",
        "workflow": "txt2txt",
        "model_name": "random/lfm2_tiny",
        "prompt": "ab cd ab ba",
        "max_new_tokens": 4,
        "num_return_sequences": 2,
        "logprobs": True,
        "content_type": "application/json",
    },
    "tts": {
        # the reference's bark smoke job (swarm/test.py:45-51)
        "id": "smoke-tts",
        "workflow": "txt2audio",
        "model_name": "random/tiny_tts",
        "prompt": "hello from the swarm",
        "audio_length_in_s": 0.3,
        "content_type": "audio/wav",
    },
    "cascade": {
        "id": "smoke-cascade",
        "model_name": "DeepFloyd/tiny_cascade",
        "prompt": "a crystal fox",
        "num_inference_steps": 2,
        "sr_steps": 2,
        "upscale": False,
        "content_type": "image/png",
    },
    "img2vid": {
        # image-to-video (SVD-class; beyond the reference — BASELINE.json
        # config #5's model class), frame injected instead of a
        # start_image_uri (no network in smoke)
        "id": "smoke-img2vid",
        "workflow": "img2vid",
        "model_name": "random/tiny_svd",
        "num_frames": 8,
        "num_inference_steps": 2,
        "height": 64, "width": 64,
        "content_type": "video/mp4",
        "_inject_image": True,
    },
    "vid2vid": {
        # the reference's vid2vid smoke job (swarm/test.py:24-33), with
        # frames injected instead of a video_uri (no network in smoke)
        "id": "smoke-vid2vid",
        "workflow": "vid2vid",
        "model_name": "tiny",
        "prompt": "make it watercolor",
        "num_inference_steps": 2,
        "strength": 0.5,
        "content_type": "video/mp4",
        "_inject_frames": True,
    },
    "stitch": {
        "id": "smoke-stitch",
        "workflow": "stitch",
        "model_name": "stitch",
        "content_type": "image/png",
        "_inject_stitch_images": True,
    },
}


def run_smoke(workflow: str, random_weights: bool = True) -> dict[str, Any]:
    import numpy as np

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.executor import synchronous_do_work
    from chiaswarm_tpu.node.registry import ModelRegistry

    job = dict(SMOKE_JOBS[workflow])
    if job.pop("_inject_image", False):
        rng = np.random.default_rng(0)
        job["image"] = rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)
    if job.pop("_inject_frames", False):
        job["frames"] = [np.full((64, 64, 3), 30 * i, np.uint8)
                         for i in range(3)]
        job["fps"] = 8.0
    if job.pop("_inject_stitch_images", False):
        from PIL import Image

        job["jobs"] = [{"resultUri": f"smoke://{i}"} for i in range(3)]
        job["images"] = [Image.new("RGB", (64, 64), (40 * i, 20, 20))
                         for i in range(3)]

    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny"},
                 {"name": "random/deepseek_tiny", "stack": "deepseek",
                  "prefill_chunk": 8, "max_context": 32},
                 {"name": "random/laguna_tiny", "stack": "laguna",
                  "prefill_chunk": 16, "max_context": 64},
                 {"name": "random/solar_tiny", "stack": "solar",
                  "prefill_chunk": 16, "max_context": 64},
                 {"name": "random/lfm2_tiny", "stack": "lfm2",
                  "prefill_chunk": 16, "max_context": 64}],
        allow_random=random_weights,
    )
    pool = ChipPool(n_slots=1)
    return synchronous_do_work(job, pool.slots[0], registry)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workflow", choices=sorted(SMOKE_JOBS),
                        default="txt2img")
    parser.add_argument("--all", action="store_true",
                        help="run every workflow's smoke job")
    parser.add_argument("--random-weights", action="store_true",
                        default=True)
    args = parser.parse_args(argv)

    workflows = sorted(SMOKE_JOBS) if args.all else [args.workflow]
    failures = 0
    for wf in workflows:
        result = run_smoke(wf, args.random_weights)
        config = result.get("pipeline_config", {})
        status = "error" if "error" in config else "ok"
        line = {
            "workflow": wf, "status": status,
            "fatal": bool(result.get("fatal_error")),
            "artifacts": sorted(result.get("artifacts", {})),
        }
        if status == "error":
            line["error"] = config["error"]
            failures += 1
        print(json.dumps(line))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
