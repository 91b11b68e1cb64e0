"""ControlNet input preprocessors (host-side, CPU).

Capability parity with swarm/controlnet/input_processor.py:17-272: the
conditioning image is computed *before* generation from the user's input
image, dispatched on ``controlnet["type"]``. These are CPU ops (OpenCV /
PIL) by design — the reference keeps them off-GPU and we keep them off-TPU
(SURVEY.md §2: "keep on CPU (host) — not TPU work").

Implemented without controlnet_aux. Exact ports: canny (cv2.Canny with
per-job thresholds), tile (scale min-dim to 1024, round to 64 multiple),
pix2pix (passthrough), shuffle (content shuffle). Every learned mode runs
a NATIVE network when its converted weights are in the model dir
(`swarm-tpu init` provisions all of them): openpose (models/openpose.py,
raises with a fetch hint when absent); scribble/softedge (models/hed.py);
depth/normalbae (models/dpt.py); seg (models/upernet.py); mlsd
(models/mlsd.py); lineart (models/lineart.py). The non-openpose modes
fall back to documented model-free stand-ins on weightless nodes
(blurred Scharr, position-prior pseudo-depth, mean-shift posterization
onto the ADE20K palette, probabilistic Hough segments, dodge-sketch).
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np
from PIL import Image

_PREPROCESSORS: dict[str, Callable[..., Image.Image]] = {}
# modes whose function takes the job's controlnet dict as a second
# positional arg (decided ONCE at registration from the signature, so
# new parametrized modes need no dispatcher special case)
_TAKES_PARAMS: set[str] = set()


def _register(name: str):
    def wrap(fn):
        import inspect

        _PREPROCESSORS[name] = fn
        params = [p for p in inspect.signature(fn).parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY,
                                p.POSITIONAL_OR_KEYWORD)]
        if len(params) > 1 and params[1].name == "controlnet":
            _TAKES_PARAMS.add(name)
        return fn
    return wrap


@_register("canny")
def image_to_canny(image: Image.Image,
                   controlnet: dict | None = None) -> Image.Image:
    """Canny edges honoring the job's thresholds
    (input_processor.py:74-84: controlnet.get("low_threshold"/
    "high_threshold") with 100/200 defaults)."""
    import cv2

    controlnet = controlnet or {}
    arr = np.asarray(image)
    edges = cv2.Canny(arr,
                      int(controlnet.get("low_threshold", 100)),
                      int(controlnet.get("high_threshold", 200)))
    return Image.fromarray(np.stack([edges] * 3, axis=-1))


def _lazy_detector(cache: list, local_name: str, loader,
                   fallback_msg: str):
    """Shared weight-gated singleton for the learned preprocessors: load
    the converted checkpoint from the model dir on first use, else cache
    ``None`` (-> caller falls back to its model-free stand-in)."""
    if not cache:
        from chiaswarm_tpu.node.registry import model_dir

        ckpt = model_dir(local_name)
        if ckpt.exists():
            cache.append(loader(ckpt))
        else:
            import logging

            logging.getLogger("chiaswarm.preprocess").info(
                "no %s weights at %s; %s", local_name, ckpt, fallback_msg)
            cache.append(None)
    return cache[0]


_HED: list[Any] = []  # resident detector (lazy; [None] = no weights)


@_register("scribble")
@_register("softedge")
def image_to_soft_edges(image: Image.Image) -> Image.Image:
    """Soft-edge map for the HED/PidiNet modes (input_processor.py:17-60).
    With converted ``ControlNetHED`` weights in the model dir this runs
    the native HED network (models/hed.py); without them it falls back to
    the model-free blurred-Scharr stand-in (logged once)."""
    import cv2

    def _load(ckpt):
        from chiaswarm_tpu.models.hed import HEDDetector

        return HEDDetector.from_checkpoint(ckpt)

    det = _lazy_detector(_HED, "hed", _load,
                         "scribble/softedge use the gradient stand-in")
    if det is not None:
        edge = det(np.asarray(image.convert("RGB")))
        return Image.fromarray(np.stack([edge] * 3, axis=-1))

    gray = cv2.cvtColor(np.asarray(image), cv2.COLOR_RGB2GRAY)
    gray = cv2.GaussianBlur(gray, (5, 5), 0)
    gx = cv2.Scharr(gray, cv2.CV_32F, 1, 0)
    gy = cv2.Scharr(gray, cv2.CV_32F, 0, 1)
    mag = np.sqrt(gx ** 2 + gy ** 2)
    mag = (255.0 * mag / max(float(mag.max()), 1e-6)).astype(np.uint8)
    return Image.fromarray(np.stack([mag] * 3, axis=-1))


@_register("tile")
def image_to_tile(image: Image.Image, resolution: int = 1024) -> Image.Image:
    """Scale so the SHORT side hits ``resolution`` (upscaling small
    inputs — tile conditioning wants detail at output scale), then round
    each side to the nearest 64 multiple (input_processor.py:63-71)."""
    w, h = image.size
    k = float(resolution) / min(h, w)
    w = max(64, int(round(w * k / 64.0)) * 64)
    h = max(64, int(round(h * k / 64.0)) * 64)
    return image.resize((w, h), Image.Resampling.LANCZOS)


@_register("pix2pix")
def image_passthrough(image: Image.Image) -> Image.Image:
    return image


@_register("shuffle")
def image_shuffle(image: Image.Image) -> Image.Image:
    """Content shuffle: coarse spatial scramble of 32px blocks."""
    rng = np.random.default_rng(0)
    arr = np.asarray(image).copy()
    h, w = arr.shape[:2]
    bs = 32
    blocks = [(y, x) for y in range(0, h - bs + 1, bs)
              for x in range(0, w - bs + 1, bs)]
    perm = rng.permutation(len(blocks))
    out = arr.copy()
    for (y, x), p in zip(blocks, perm):
        sy, sx = blocks[p]
        out[y:y + bs, x:x + bs] = arr[sy:sy + bs, sx:sx + bs]
    return Image.fromarray(out)


_MLSD: list[Any] = []  # resident detector (lazy; [None] = no weights)


@_register("mlsd")
def image_to_line_segments(image: Image.Image) -> Image.Image:
    """Wireframe map for the mlsd mode (input_processor.py:17-60). With
    converted ``MobileV2_MLSD_Large`` weights in the model dir this runs
    the native M-LSD network (models/mlsd.py); without them it falls back
    to the model-free Hough stand-in (logged once)."""
    import cv2

    def _load(ckpt):
        from chiaswarm_tpu.models.mlsd import MLSDDetector

        return MLSDDetector.from_checkpoint(ckpt)

    det = _lazy_detector(_MLSD, "mlsd", _load,
                         "mlsd uses the Hough-segments stand-in")
    arr = np.asarray(image)
    if det is not None:
        wire = det(arr)
        return Image.fromarray(np.stack([wire] * 3, axis=-1))

    gray = cv2.cvtColor(arr, cv2.COLOR_RGB2GRAY)
    edges = cv2.Canny(gray, 50, 150)
    lines = cv2.HoughLinesP(edges, 1, np.pi / 180, threshold=40,
                            minLineLength=24, maxLineGap=4)
    out = np.zeros_like(arr)
    if lines is not None:
        for x1, y1, x2, y2 in np.asarray(lines).reshape(-1, 4):
            cv2.line(out, (x1, y1), (x2, y2), (255, 255, 255), 2)
    return Image.fromarray(out)


_LINEART: list[Any] = []  # resident detector (lazy; [None] = no weights)


@_register("lineart")
def image_to_lineart(image: Image.Image) -> Image.Image:
    """Line drawing for the lineart mode (input_processor.py:17-60). With
    converted informative-drawings ``Generator`` weights in the model dir
    this runs the native network (models/lineart.py); without them it
    falls back to the model-free dodge-blend sketch (logged once)."""
    import cv2

    def _load(ckpt):
        from chiaswarm_tpu.models.lineart import LineartDetector

        return LineartDetector.from_checkpoint(ckpt)

    det = _lazy_detector(_LINEART, "lineart", _load,
                         "lineart uses the dodge-sketch stand-in")
    if det is not None:
        lines = det(np.asarray(image.convert("RGB")))
        return Image.fromarray(np.stack([lines] * 3, axis=-1))

    gray = cv2.cvtColor(np.asarray(image), cv2.COLOR_RGB2GRAY)
    blur = cv2.GaussianBlur(gray, (21, 21), 0)
    sketch = cv2.divide(gray, np.maximum(blur, 1), scale=256)
    lines = 255 - sketch  # dark strokes -> bright lines
    lines = cv2.normalize(lines, None, 0, 255, cv2.NORM_MINMAX)
    return Image.fromarray(np.stack([lines.astype(np.uint8)] * 3, axis=-1))


_DPT: list[Any] = []  # resident depth model (lazy; [None] = no weights)


def _pseudo_depth(arr: np.ndarray) -> np.ndarray:
    """Model-free MiDaS stand-in: vertical position prior (lower in frame ~
    nearer) blended with local sharpness (in-focus ~ nearer). float [0,1],
    1 = near."""
    import cv2

    gray = cv2.cvtColor(arr, cv2.COLOR_RGB2GRAY).astype(np.float32) / 255.0
    h, w = gray.shape
    position = np.linspace(0.0, 1.0, h)[:, None].repeat(w, axis=1)
    lap = np.abs(cv2.Laplacian(gray, cv2.CV_32F, ksize=5))
    sharp = cv2.GaussianBlur(lap, (0, 0), sigmaX=max(h, w) / 32.0)
    sharp = sharp / max(float(sharp.max()), 1e-6)
    depth = (0.6 * position + 0.4 * sharp).astype(np.float32)
    return cv2.GaussianBlur(depth, (0, 0), sigmaX=3.0)


def _depth_map(arr: np.ndarray) -> np.ndarray:
    """float depth in [0, 1] (1 = near): the native DPT network
    (models/dpt.py — the same architecture behind the reference's
    transformers depth pipeline, input_processor.py:87-93) when converted
    weights exist in the model dir, else the model-free stand-in."""
    def _load(ckpt):
        from chiaswarm_tpu.models.dpt import DPTDetector

        return DPTDetector.from_checkpoint(ckpt)

    det = _lazy_detector(_DPT, "dpt", _load,
                         "depth/normal use the position-prior stand-in")
    if det is not None:
        d = det.depth(arr)
        lo, hi = float(d.min()), float(d.max())
        return ((d - lo) / max(hi - lo, 1e-6)).astype(np.float32)
    return _pseudo_depth(arr)


@_register("depth")
def image_to_depth(image: Image.Image) -> Image.Image:
    depth = _depth_map(np.asarray(image))
    u8 = (depth * 255.0).clip(0, 255).astype(np.uint8)
    return Image.fromarray(np.stack([u8] * 3, axis=-1))


@_register("normal")
@_register("normalbae")
def image_to_normal(image: Image.Image) -> Image.Image:
    """Surface normals from the pseudo-depth via Sobel gradients, encoded
    in the usual RGB = (x, y, z) * 0.5 + 0.5 convention."""
    import cv2

    depth = _depth_map(np.asarray(image))
    dx = cv2.Sobel(depth, cv2.CV_32F, 1, 0, ksize=5)
    dy = cv2.Sobel(depth, cv2.CV_32F, 0, 1, ksize=5)
    z = np.full_like(depth, 0.1)
    norm = np.sqrt(dx * dx + dy * dy + z * z)
    n = np.stack([-dx / norm, -dy / norm, z / norm], axis=-1)
    return Image.fromarray(((n * 0.5 + 0.5) * 255).clip(0, 255)
                           .astype(np.uint8))


# full ADE20K palette (the 150-class table + background row the reference
# embeds at input_processor.py:118-272), shared with models/upernet.py
from chiaswarm_tpu.models.ade_palette import (  # noqa: E402
    ADE20K_PALETTE as _ADE_PALETTE,
)


_SEG: list[Any] = []  # resident segmenter (lazy; [None] = no weights)


@_register("seg")
def image_to_segments(image: Image.Image) -> Image.Image:
    """ADE-colored segmentation map. With converted UperNet-ConvNeXt
    weights in the model dir this runs the native model the reference
    calls through transformers (models/upernet.py,
    input_processor.py:96-115); without them: mean-shift posterization
    with each region color snapped to the nearest ADE-palette entry."""
    import cv2

    def _load(ckpt):
        from chiaswarm_tpu.models.upernet import UperNetDetector

        return UperNetDetector.from_checkpoint(ckpt)

    det = _lazy_detector(_SEG, "upernet", _load,
                         "seg uses the posterization stand-in")
    if det is not None:
        return Image.fromarray(det(np.asarray(image.convert("RGB"))))

    arr = cv2.pyrMeanShiftFiltering(
        cv2.cvtColor(np.asarray(image), cv2.COLOR_RGB2BGR), 12, 24)
    arr = cv2.cvtColor(arr, cv2.COLOR_BGR2RGB)
    flat = arr.reshape(-1, 3).astype(np.float32)
    pal = _ADE_PALETTE.astype(np.float32)
    # ||a-b||^2 = ||a||^2 - 2 a.b + ||b||^2: peak extra memory is (N, 32)
    # floats instead of an (N, 32, 3) difference tensor
    dists = ((flat ** 2).sum(1, keepdims=True)
             - 2.0 * flat @ pal.T + (pal ** 2).sum(1)[None])
    return Image.fromarray(
        _ADE_PALETTE[np.argmin(dists, axis=1)].reshape(arr.shape))


_OPENPOSE: list[Any] = []  # resident detector (lazy singleton)


@_register("openpose")
def image_to_openpose(image: Image.Image) -> Image.Image:
    """Native CMU body-pose skeleton (models/openpose.py) — the one
    preprocessor that needs learned weights. Loads ``body_pose_model``
    weights from the node's model dir (fetched by init alongside the
    diffusion checkpoints); without them this raises, matching the
    historical behavior but with an actionable message."""
    if not _OPENPOSE:
        from chiaswarm_tpu.models.openpose import OpenposeDetector
        from chiaswarm_tpu.node.registry import model_dir

        ckpt = model_dir("openpose")
        if not ckpt.exists():
            raise ValueError(
                "openpose preprocessing needs the CMU body_pose_model "
                f"weights at {ckpt}; `swarm-tpu init` fetches them when "
                "the hive catalog lists an openpose model, or place "
                "body_pose_model.pth there manually"
            )
        _OPENPOSE.append(OpenposeDetector.from_checkpoint(ckpt))
    skeleton = _OPENPOSE[0](np.asarray(image.convert("RGB")))
    return Image.fromarray(skeleton)


def preprocess_image(image: Image.Image, controlnet: dict[str, Any]) -> Image.Image:
    """Dispatch on controlnet["type"] (input_processor.py:17-60). Every
    mode has an exact port or a native detector gated on converted
    weights (with a documented model-free stand-in).

    Like the reference (input_processor.py:18), preprocessing is OFF by
    default — the server marks jobs whose input is raw and needs
    annotation; an already-annotated conditioning image passes through."""
    kind = str(controlnet.get("type", "canny")).lower()
    if not controlnet.get("preprocess", False):
        return image
    fn = _PREPROCESSORS.get(kind)
    if fn is None:
        raise ValueError(
            f"controlnet preprocessor {kind!r} is not yet supported on "
            f"this TPU worker (available: {sorted(_PREPROCESSORS)})"
        )
    if kind in _TAKES_PARAMS:
        return fn(image, controlnet)
    return fn(image)
