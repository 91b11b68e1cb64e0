"""Node bootstrap: configure hive credentials, fetch the model catalog,
prefetch + convert checkpoints, and pre-warm compiles.

Capability parity with swarm/initialize.py:19-120 (``--reset`` / ``--silent``
interactive setup, ``GET /api/models`` cached to ``models.json``, per-model
weight prefetch), plus the TPU-specific extra the reference doesn't need:
optional ahead-of-time compilation of the hot shape buckets so the first
real job doesn't pay XLA compile time.

Zero-egress environments (no hub access) skip the download step cleanly —
the registry falls back per job and `swarm-tpu smoke` still runs with
random weights.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
from typing import Any

import aiohttp

from chiaswarm_tpu.node.hive import HiveClient
from chiaswarm_tpu.node.logging_setup import setup_logging
from chiaswarm_tpu.node.registry import model_dir
from chiaswarm_tpu.node.settings import (
    Settings,
    load_settings,
    save_file,
    save_settings,
    settings_root,
)

log = logging.getLogger("chiaswarm.init")


def prompt_settings(settings: Settings) -> Settings:
    uri = input(f"hive uri [{settings.hive_uri}]: ").strip()
    token = input("hive token (blank keeps current): ").strip()
    name = input(f"worker name [{settings.worker_name}]: ").strip()
    if uri:
        settings.hive_uri = uri
    if token:
        settings.hive_token = token
    if name:
        settings.worker_name = name
    return settings


async def fetch_model_catalog(settings: Settings) -> list[dict[str, Any]]:
    hive = HiveClient(settings.hive_uri, settings.hive_token,
                      settings.worker_name)
    async with aiohttp.ClientSession() as session:
        models = await hive.get_models(session)
    save_file(models, "models.json")
    log.info("cached %d models from the hive catalog", len(models))
    return models


def prefetch_checkpoints(models: list[dict[str, Any]],
                         settings: Settings) -> int:
    """Download preloadable checkpoints into the local model store
    (reference behavior at swarm/initialize.py:62-94). Needs hub access;
    returns the number fetched."""
    try:
        from huggingface_hub import snapshot_download
    except Exception:
        log.warning("huggingface_hub unavailable; skipping prefetch")
        return 0

    fetched = 0
    for model in models:
        name = model.get("name") or model.get("model_name")
        if not name or not model.get("parameters", {}).get("can_preload",
                                                           True):
            continue
        target = model_dir(name)
        if target.exists():
            continue
        try:
            log.info("prefetching %s", name)
            snapshot_download(
                name, local_dir=str(target),
                token=settings.huggingface_token or None,
                allow_patterns=["*.safetensors", "*.json", "*.txt"],
            )
            fetched += 1
        except Exception as exc:
            log.warning("prefetch of %s failed: %s", name, exc)
    fetched += _prefetch_annotators(models, settings)
    fetched += _prefetch_safety_checker(models, settings)
    return fetched


_SAFETY_CHECKER_REPO = "CompVis/stable-diffusion-safety-checker"


def _is_sd_generation_model(model: dict[str, Any]) -> bool:
    """True for models whose outputs go through the NSFW checker —
    anything the diffusion callback serves (the reference always checks,
    swarm/diffusion/diffusion_func.py:99-111)."""
    name = str(model.get("name") or model.get("model_name") or "")
    if not name:
        return False
    from chiaswarm_tpu.pipelines.tts import is_tts_model

    if is_tts_model(name) or "audioldm" in name.lower() \
            or "blip" in name.lower():
        return False
    workflow = str((model.get("parameters") or {}).get("workflow", ""))
    return workflow not in ("txt2audio", "img2txt", "txt2txt", "txt2vid",
                            "vid2vid")


def _prefetch_safety_checker(models: list[dict[str, Any]],
                             settings: Settings) -> int:
    """Provision the standalone safety checker whenever the catalog lists
    any image-generating model (workloads/safety.py loads it from
    ``model_dir("CompVis/stable-diffusion-safety-checker")``; without it a
    node honestly reports ``safety_checker: "unavailable"`` but an open
    network should always check)."""
    if not any(_is_sd_generation_model(m) for m in models):
        return 0
    target = model_dir(_SAFETY_CHECKER_REPO)
    if target.exists():
        return 0
    tmp = target.with_name(target.name + ".fetching")
    try:
        from huggingface_hub import snapshot_download

        tmp.mkdir(parents=True, exist_ok=True)
        snapshot_download(
            _SAFETY_CHECKER_REPO, local_dir=str(tmp),
            token=settings.huggingface_token or None,
            allow_patterns=["*.safetensors", "*.bin", "*.json"],
        )
        tmp.rename(target)  # only a COMPLETE fetch claims the dir
        log.info("fetched safety checker weights")
        return 1
    except Exception as exc:
        log.warning("safety checker fetch failed: %s", exc)
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
        return 0


# learned preprocessor weights (models/openpose.py, models/hed.py,
# models/dpt.py, models/upernet.py, models/mlsd.py, models/lineart.py):
# local model-dir name -> (catalog hint words, hub repo, weight filename).
# openpose/hed/mlsd/lineart come from the public annotator mirror the
# reference's controlnet_aux uses; depth from the Intel DPT release. ALL
# six learned modes provision here — a fresh node must never silently
# serve a stand-in for a mode it could run natively.
_ANNOTATORS = {
    "openpose": (("openpose",), "lllyasviel/Annotators",
                 "body_pose_model.pth"),
    "hed": (("hed", "scribble", "softedge"), "lllyasviel/Annotators",
            "ControlNetHED.pth"),
    "dpt": (("depth", "normal", "normalbae"), "Intel/dpt-large",
            "model.safetensors"),
    "upernet": (("seg", "segmentation"), "openmmlab/upernet-convnext-small",
                "model.safetensors"),
    "mlsd": (("mlsd",), "lllyasviel/Annotators",
             "mlsd_large_512_fp32.pth"),
    "lineart": (("lineart",), "lllyasviel/Annotators", "sk_model.pth"),
}


def _prefetch_annotators(models: list[dict[str, Any]],
                         settings: Settings) -> int:
    """Fetch learned-preprocessor weights when any catalog model
    advertises a controlnet mode that needs them."""
    import re

    blob = " ".join(
        f"{m.get('name', '')} {m.get('parameters') or {}}".lower()
        for m in models)
    words = set(re.findall(r"[a-z0-9]+", blob))  # word-boundary matching:
    # a substring test would fire 'hed' on 'scheduler'/'cached'
    fetched = 0
    for local_name, (hints, repo, filename) in _ANNOTATORS.items():
        target = model_dir(local_name)
        if target.exists() or not any(h in words for h in hints):
            continue
        tmp = target.with_name(target.name + ".fetching")
        try:
            from huggingface_hub import hf_hub_download

            tmp.mkdir(parents=True, exist_ok=True)
            hf_hub_download(repo, filename,
                            local_dir=str(tmp),
                            token=settings.huggingface_token or None)
            tmp.rename(target)  # only a COMPLETE fetch claims the dir
            log.info("fetched %s annotator weights (%s)", local_name,
                     filename)
            fetched += 1
        except Exception as exc:
            log.warning("%s weight fetch failed: %s", local_name, exc)
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
    return fetched


def warm_compile(models: list[dict[str, Any]]) -> None:
    """Ahead-of-time compile the default shape bucket per local model.

    Warms the SAME cache entries serving will hit: the worker's default
    slot mesh keys the pipeline entry (node/registry.py), so warming
    without it would leave a dead unsharded duplicate and pay the full
    load+compile again on the first real job."""
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import load_settings
    from chiaswarm_tpu.pipelines.diffusion import GenerateRequest

    settings = load_settings()
    from chiaswarm_tpu.core.mesh import MeshSpec

    spec = (MeshSpec(dict(settings.mesh_shape))
            if settings.mesh_shape else None)
    mesh = ChipPool(n_slots=1, mesh_spec=spec).slots[0].mesh
    registry = ModelRegistry(catalog=models, allow_random=False)
    for model in models:
        name = model.get("name") or model.get("model_name")
        if not name or not model_dir(name).exists():
            continue
        try:
            workflow = str((model.get("parameters") or {})
                           .get("workflow", ""))
            # bark outranks the txt2audio workflow tag: the hive serves
            # bark UNDER txt2audio (job_args.py routing), so the name
            # gate must win or bark would warm as AudioLDM and fail
            from chiaswarm_tpu.pipelines.tts import is_tts_model

            if is_tts_model(name):
                registry.tts_pipeline(name)("warmup", duration_s=0.5)
            elif name.startswith("DeepFloyd/"):
                registry.cascade_pipeline(name, mesh=mesh)(
                    "warmup", steps=2, sr_steps=2)
            elif workflow == "txt2audio" or "audioldm" in name.lower():
                registry.audio_pipeline(name)("warmup", steps=2,
                                              duration_s=1.0)
            elif workflow == "img2txt" or "blip" in name.lower():
                import numpy as np

                registry.caption_pipeline(name, mesh=mesh)(
                    np.zeros((64, 64, 3), np.uint8))
            else:
                pipe = registry.pipeline(name, mesh=mesh)
                size = pipe.c.family.default_size
                pipe(GenerateRequest(prompt="warmup", steps=2,
                                     height=size, width=size, seed=0))
            log.info("warmed %s", name)
        except Exception as exc:
            log.warning("warm compile of %s failed: %s", name, exc)


async def init(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--reset", action="store_true",
                        help="re-prompt for hive uri/token")
    parser.add_argument("--silent", action="store_true",
                        help="no prompts; use existing/env settings")
    parser.add_argument("--no-prefetch", action="store_true")
    parser.add_argument("--warm-compile", action="store_true")
    args = parser.parse_args(argv)

    settings = load_settings()
    setup_logging(settings_root() / "logs", settings.log_filename,
                  settings.log_level)
    if args.reset or (not settings.hive_token and not args.silent):
        settings = prompt_settings(settings)
    save_settings(settings)

    try:
        models = await fetch_model_catalog(settings)
    except Exception as exc:
        log.warning("could not reach the hive (%s); using cached catalog",
                    exc)
        from chiaswarm_tpu.node.settings import load_file

        models = load_file("models.json") or []

    if not args.no_prefetch:
        prefetch_checkpoints(models, settings)
    if args.warm_compile:
        warm_compile(models)
    log.info("init complete: settings at %s", settings_root())
    return 0


def main() -> None:
    raise SystemExit(asyncio.run(init()))


if __name__ == "__main__":
    main()
