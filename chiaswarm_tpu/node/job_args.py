"""Job dispatch + argument normalization — THE routing table.

Capability parity with swarm/job_arguments.py:17-190: a hive job dict maps
to ``(callback, kwargs)`` by workflow; stable-diffusion jobs get their
inputs rationalized (size clamp, input-image fetch with guards, ControlNet
rewiring, instruct-pix2pix strength remap, default steps, server-listed
unsupported-argument stripping).

TPU-first differences: the server's diffusers *class names* don't resolve
to classes here — ``pipeline_type`` folds into the unified jitted pipeline's
static mode flags and ``scheduler_type`` maps through
schedulers.resolve (same server contract, no dynamic imports); a
``registry`` (node/registry.py) rides along so callbacks bind resident
compiled models instead of loading weights per job.
"""

from __future__ import annotations

import io
import logging
from typing import Any, Callable

import numpy as np
from PIL import Image, ImageOps

from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.resilience import BadAssetError

log = logging.getLogger("chiaswarm.dispatch")

MAX_SIZE = 1024
MAX_IMAGE_BYTES = 3 * 1048576   # input guard, job_arguments.py:172-176
DEFAULT_STEPS = 30              # job_arguments.py:139-141

# ---- asset trust-boundary hardening (ISSUE 10 satellite) ----
# Asset fetches cross an open-network trust boundary with hostile
# parties on the far side. Beyond the reference's byte cap: explicit
# connect/read timeouts (a stalling asset host must not wedge an
# executor thread into its job deadline), a STREAMED read capped at
# MAX_IMAGE_BYTES (a body larger than its Content-Length claim is cut
# off without buffering it), and a decoded-pixel-dimension cap (a
# 20 KB PNG claiming 30000x30000 pixels is a decompression bomb — PIL
# exposes the dimensions before decoding, so the bomb never inflates).
# Violations raise resilience.BadAssetError -> non-fatal "bad_asset";
# network faults stay "transient" (the PR-2 taxonomy).
CONNECT_TIMEOUT_S = 10.0
READ_TIMEOUT_S = 60.0
MAX_IMAGE_PIXELS = 16 * 1024 * 1024  # 16 Mpx; served max is ~1 Mpx

FormatResult = tuple[Callable[..., tuple[dict, dict]], dict[str, Any]]


def format_args(job: dict[str, Any], registry: ModelRegistry) -> FormatResult:
    """Route one hive job. Raises on malformed input (treated as a fatal,
    non-retryable error by the executor — swarm/generator.py:34-41)."""
    args = dict(job)
    args["registry"] = registry
    workflow = args.pop("workflow", None)

    if workflow == "txt2audio":
        from chiaswarm_tpu.workloads.audio import (
            tts_callback, txt2audio_callback,
        )

        from chiaswarm_tpu.pipelines.tts import is_tts_model

        if is_tts_model(str(args.get("model_name", ""))):
            return tts_callback, args
        return _format_audio_args(args)

    if workflow == "stitch":
        from chiaswarm_tpu.workloads.stitch import stitch_callback

        return stitch_callback, args

    if workflow == "img2txt":
        from chiaswarm_tpu.workloads.caption import caption_callback

        if "start_image_uri" in args:
            args["image"] = np.asarray(
                get_image(args.pop("start_image_uri"), None)
            )
        return caption_callback, args

    if workflow == "txt2txt":
        from chiaswarm_tpu.workloads.text import text_callback

        # the sampling knobs may ride the hive's ``parameters`` dict
        parameters = args.pop("parameters", None) or {}
        for name in ("max_new_tokens", "num_return_sequences",
                     "temperature", "logprobs"):
            if name in parameters:
                args.setdefault(name, parameters[name])
        return text_callback, args

    if workflow == "vid2vid":
        from chiaswarm_tpu.workloads.video import vid2vid_callback

        return vid2vid_callback, args

    if workflow == "img2vid":
        from chiaswarm_tpu.workloads.video import img2vid_callback

        parameters = _pop_parameters(args)
        args.pop("prompt", None)        # image-conditioned: no text tower
        args["scheduler_type"] = parameters.pop("scheduler_type", None)
        _strip_unsupported(args, parameters)
        if "start_image_uri" in args:
            args["image"] = np.asarray(
                get_image(args.pop("start_image_uri"), None))
        return img2vid_callback, args

    if workflow == "txt2vid":
        from chiaswarm_tpu.workloads.video import txt2vid_callback

        return _format_txt2vid_args(args)

    if str(args.get("model_name", "")).startswith("DeepFloyd/"):
        from chiaswarm_tpu.workloads.cascade import cascade_callback

        return cascade_callback, args

    return _format_stable_diffusion_args(args)


def _pop_parameters(args: dict[str, Any]) -> dict[str, Any]:
    parameters = args.pop("parameters", {}) or {}
    args.setdefault("prompt", "")
    return parameters


def _strip_unsupported(args: dict[str, Any], parameters: dict[str, Any]) -> None:
    """Server-driven capability negotiation (job_arguments.py:150-151)."""
    for name in parameters.get("unsupported_pipeline_arguments", []):
        args.pop(name, None)


def _format_audio_args(args: dict[str, Any]) -> FormatResult:
    from chiaswarm_tpu.workloads.audio import txt2audio_callback

    parameters = _pop_parameters(args)
    # AudioLDM default is 20 steps (swarm/audio/audioldm.py:15-16)
    args.setdefault("num_inference_steps", 20)
    args["scheduler_type"] = parameters.pop("scheduler_type", None)
    _strip_unsupported(args, parameters)
    return txt2audio_callback, args


def _format_txt2vid_args(args: dict[str, Any]) -> FormatResult:
    from chiaswarm_tpu.workloads.video import txt2vid_callback

    parameters = _pop_parameters(args)
    args.setdefault("num_inference_steps", 25)
    args.pop("num_images_per_prompt", None)
    args["scheduler_type"] = parameters.pop("scheduler_type", None)
    _strip_unsupported(args, parameters)
    return txt2vid_callback, args


def _format_stable_diffusion_args(args: dict[str, Any]) -> FormatResult:
    from chiaswarm_tpu.workloads.diffusion import diffusion_callback

    size = None
    if "height" in args and "width" in args:
        size = (int(args["height"]), int(args["width"]))
        if size[0] > MAX_SIZE or size[1] > MAX_SIZE:
            raise ValueError(
                f"The max image size is ({MAX_SIZE}, {MAX_SIZE}); "
                f"got ({size[0]}, {size[1]})."
            )

    parameters = _pop_parameters(args)
    args["upscale"] = parameters.get("upscale", False)

    if "start_image_uri" in args:
        args.pop("height", None)
        args.pop("width", None)
        controlnet = parameters.get("controlnet")
        image = get_image(args.pop("start_image_uri"), size, controlnet)
        args["image"] = np.asarray(image)

        if controlnet is not None:
            args["controlnet_model_name"] = controlnet.get(
                "controlnet_model_name", "lllyasviel/control_v11p_sd15_canny"
            )
            args["save_preprocessed_input"] = controlnet.get("preprocess",
                                                             False)
        if args.get("model_name") == "timbrooks/instruct-pix2pix":
            # pix2pix conditions on image_guidance_scale (1-5), the hive
            # sends strength (0-1) — same remap as job_arguments.py:128-131
            args["image_guidance_scale"] = args.pop("strength", 0.6) * 5

    if "mask_image_uri" in args:
        args.pop("height", None)
        args.pop("width", None)
        mask = get_image(args.pop("mask_image_uri"), size)
        args["mask_image"] = np.asarray(mask)

    args.setdefault("num_inference_steps", DEFAULT_STEPS)
    # server-named diffusers scheduler class -> our sampler registry
    args["scheduler_type"] = parameters.pop("scheduler_type", None)
    # DeepCache step-level reuse (ISSUE 12): a per-job schedule (list of
    # ladder indices or "every:N"); tuple-ized so the burst coalescer
    # can hash it as part of COALESCE_KEYS
    reuse = parameters.pop("reuse_schedule", None)
    if reuse is not None:
        args["reuse_schedule"] = (tuple(reuse)
                                  if isinstance(reuse, (list, tuple))
                                  else reuse)
    _strip_unsupported(args, parameters)
    return diffusion_callback, args


# ---- input fetching with trust-boundary guards ------------------------


def _read_capped(response, cap: int) -> bytes:
    """Stream a response body up to ``cap`` bytes; one byte more is a
    :class:`BadAssetError` — the body is never buffered past the cap,
    so a hostile server cannot make this worker hold a multi-GB asset
    in memory no matter what Content-Length it claimed."""
    chunks: list[bytes] = []
    total = 0
    for chunk in response.iter_content(chunk_size=65536):
        total += len(chunk)
        if total > cap:
            raise BadAssetError(
                f"Input image too large.\nMax size is {cap} bytes.\n"
                f"Stream exceeded the cap at {total} bytes.")
        chunks.append(chunk)
    return b"".join(chunks)


def _check_decoded_dims(image: Image.Image) -> None:
    """Decompression-bomb guard: PIL exposes the claimed dimensions
    before decoding any pixels — reject the bomb while it is still a
    few KB of compressed bytes."""
    pixels = int(image.size[0]) * int(image.size[1])
    if pixels > MAX_IMAGE_PIXELS:
        raise BadAssetError(
            f"Input image decodes to {image.size[0]}x{image.size[1]} "
            f"({pixels} pixels), over the {MAX_IMAGE_PIXELS}-pixel cap "
            f"(decompression-bomb guard).")


def download_image(url: str,
                   max_bytes: int = MAX_IMAGE_BYTES) -> Image.Image:
    """Guarded image fetch. ``max_bytes`` defaults to the user-INPUT
    cap; callers fetching the system's own outputs (stitch pulls prior
    RESULT images, which an upscaled 2048px PNG legitimately pushes
    past 3 MiB) pass a larger cap — the decoded-dimension bomb guard
    and content-type/timeout checks still apply unchanged."""
    import requests

    # the context manager closes the streamed response on EVERY path —
    # a guard violation raised mid-stream must not leave the pooled
    # connection checked out until GC (a burst of hostile assets would
    # otherwise pin one dead socket per executor thread)
    with requests.get(url, allow_redirects=True, stream=True,
                      timeout=(CONNECT_TIMEOUT_S,
                               READ_TIMEOUT_S)) as response:
        response.raise_for_status()
        content_type = response.headers.get("Content-Type", "")
        if content_type and not content_type.startswith("image"):
            # the GET's own content type — a host that passed the HEAD
            # check must not switch to text/html for the real body
            raise BadAssetError(
                "Input does not appear to be an image.\n"
                f"Content type was {content_type}.")
        # streamed + capped: Content-Length can be absent or forged; a
        # compliant header says nothing about the body that follows
        data = _read_capped(response, max_bytes)
    image = Image.open(io.BytesIO(data))
    _check_decoded_dims(image)
    image = ImageOps.exif_transpose(image)
    return image.convert("RGB")


def get_image(uri: str, size: tuple[int, int] | None,
              controlnet: dict | None = None) -> Image.Image:
    """Fetch an input image with the open-network guards the reference
    enforces (job_arguments.py:162-190) plus the ISSUE-10 hardening:
    content-type must be an image, payload streamed and capped at 3 MiB,
    decoded dimensions capped (decompression-bomb guard), explicit
    connect/read timeouts, downscaled to the requested / max size.
    Guard violations raise :class:`BadAssetError` (non-fatal
    ``bad_asset``); network faults classify ``transient``."""
    import requests

    head = requests.head(uri, allow_redirects=True,
                         timeout=(CONNECT_TIMEOUT_S, 30.0))
    content_type = head.headers.get("Content-Type", "")
    content_length = int(head.headers.get("Content-Length", 0) or 0)
    if not content_type.startswith("image"):
        raise BadAssetError(
            "Input does not appear to be an image.\n"
            f"Content type was {content_type}."
        )
    if content_length > MAX_IMAGE_BYTES:
        raise BadAssetError(
            f"Input image too large.\nMax size is {MAX_IMAGE_BYTES} bytes.\n"
            f"Image was {content_length}."
        )

    image = download_image(uri)
    if size is not None and (image.height > size[0] or image.width > size[1]):
        # PIL thumbnail takes (max_width, max_height); size is (H, W)
        image.thumbnail((size[1], size[0]), Image.Resampling.LANCZOS)
    elif image.height > MAX_SIZE or image.width > MAX_SIZE:
        image.thumbnail((MAX_SIZE, MAX_SIZE), Image.Resampling.LANCZOS)

    if controlnet is not None:
        from chiaswarm_tpu.workloads.controlnet import preprocess_image

        image = preprocess_image(image, controlnet)
    return image
