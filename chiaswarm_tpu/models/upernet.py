"""UperNet semantic segmentation (ConvNeXt backbone) — the seg preprocessor.

The reference's seg ControlNet mode runs UperNet over the ADE20K classes
(swarm/controlnet/input_processor.py:96-115, the transformers
``UperNetForSemanticSegmentation`` checkpoints); this is the same model
natively: a ConvNeXt backbone tapped at all four stages, the PSP pyramid
pooling module, the FPN top-down path, and the fused classifier head.
Weights convert 1:1 from the HF state dict (convert/torch_to_flax.py::
convert_upernet), fidelity-tested against torch.

TPU notes: one fixed canvas per checkpoint (single compiled program);
adaptive average pooling and the align-corners-false bilinear resizes are
einsum contractions against constant interpolation matrices (MXU-
friendly, no gathers); BatchNorms run in inference form from their
converted running statistics. The argmax class map leaves the chip as
uint8; the ADE palette lookup is host-side.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class UperNetConfig:
    # ConvNeXt backbone (openmmlab/upernet-convnext-small defaults)
    depths: Sequence[int] = (3, 3, 27, 3)
    hidden_sizes: Sequence[int] = (96, 192, 384, 768)
    layer_scale: bool = True
    # decode head
    channels: int = 512
    pool_scales: Sequence[int] = (1, 2, 3, 6)
    num_labels: int = 150
    image_size: int = 512
    dtype: str = "float32"


UPERNET_CONVNEXT_SMALL = UperNetConfig()

UPERNET_TINY = UperNetConfig(depths=(1, 1, 1, 1),
                             hidden_sizes=(8, 16, 24, 32), channels=16,
                             num_labels=10, image_size=64)

UPERNET_CONFIGS = {"upernet_convnext_small": UPERNET_CONVNEXT_SMALL,
                   "upernet_tiny": UPERNET_TINY}


def _resize_matrix(n_in: int, n_out: int) -> np.ndarray:
    """(n_out, n_in) bilinear weights, half-pixel centers (torch
    ``interpolate(..., align_corners=False)``)."""
    w = np.zeros((n_out, n_in), np.float32)
    pos = (np.arange(n_out) + 0.5) * n_in / n_out - 0.5
    pos = pos.clip(0, n_in - 1)
    lo = np.floor(pos).astype(np.int64)
    hi = np.minimum(lo + 1, n_in - 1)
    frac = (pos - lo).astype(np.float32)
    w[np.arange(n_out), lo] += 1.0 - frac
    w[np.arange(n_out), hi] += frac
    return w


def _adaptive_pool_matrix(n_in: int, n_out: int) -> np.ndarray:
    """(n_out, n_in) averaging weights matching torch
    ``adaptive_avg_pool2d`` window placement."""
    w = np.zeros((n_out, n_in), np.float32)
    for o in range(n_out):
        start = (o * n_in) // n_out
        end = -(-(o + 1) * n_in // n_out)
        w[o, start:end] = 1.0 / (end - start)
    return w


def _apply_sep(x: jnp.ndarray, wh: np.ndarray, ww: np.ndarray) -> jnp.ndarray:
    """(B, H, W, C) x separable row/col weight matrices."""
    x = jnp.einsum("oh,bhwc->bowc", jnp.asarray(wh), x)
    return jnp.einsum("pw,bowc->bopc", jnp.asarray(ww), x)


def resize_bilinear(x: jnp.ndarray, size: tuple[int, int]) -> jnp.ndarray:
    b, h, w, c = x.shape
    if (h, w) == size:
        return x
    return _apply_sep(x, _resize_matrix(h, size[0]),
                      _resize_matrix(w, size[1]))


def adaptive_avg_pool(x: jnp.ndarray, scale: int) -> jnp.ndarray:
    b, h, w, c = x.shape
    return _apply_sep(x, _adaptive_pool_matrix(h, scale),
                      _adaptive_pool_matrix(w, scale))


class ConvNextLayer(nn.Module):
    dim: int
    layer_scale: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        residual = x
        h = nn.Conv(self.dim, (7, 7), padding=3,
                    feature_group_count=self.dim, dtype=self.dtype,
                    name="dwconv")(x)
        h = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                         name="layernorm")(h).astype(self.dtype)
        h = nn.Dense(4 * self.dim, dtype=self.dtype, name="pwconv1")(h)
        h = nn.gelu(h, approximate=False)
        h = nn.Dense(self.dim, dtype=self.dtype, name="pwconv2")(h)
        if self.layer_scale:
            gamma = self.param("layer_scale_parameter",
                               nn.initializers.ones, (self.dim,))
            h = h * gamma.astype(self.dtype)
        return residual + h


class BNConv(nn.Module):
    """UperNetConvModule: conv (no bias) + inference BatchNorm + ReLU."""

    channels: int
    kernel: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        h = nn.Conv(self.channels, (self.kernel, self.kernel),
                    padding=self.kernel // 2, use_bias=False,
                    dtype=self.dtype, name="conv")(x)
        scale = self.param("bn_scale", nn.initializers.ones,
                           (self.channels,))
        bias = self.param("bn_bias", nn.initializers.zeros,
                          (self.channels,))
        mean = self.param("bn_mean", nn.initializers.zeros,
                          (self.channels,))
        var = self.param("bn_var", nn.initializers.ones, (self.channels,))
        h = (h.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + 1e-5)
        return nn.relu((h * scale + bias).astype(self.dtype))


class UperNetSeg(nn.Module):
    """(B, S, S, 3) normalized pixels -> (B, S, S) uint8 class ids."""

    config: UperNetConfig

    @property
    def dtype(self) -> jnp.dtype:
        return jnp.dtype(self.config.dtype)

    @nn.compact
    def __call__(self, pixel_values: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dtype = self.dtype
        x = pixel_values.astype(dtype)

        # ---- ConvNeXt backbone
        x = nn.Conv(cfg.hidden_sizes[0], (4, 4), strides=(4, 4),
                    dtype=dtype, name="patch_embed")(x)
        x = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                         name="embed_norm")(x).astype(dtype)
        features = []
        for s, (depth, dim) in enumerate(zip(cfg.depths, cfg.hidden_sizes)):
            if s > 0:
                x = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                                 name=f"down_norm_{s}")(x).astype(dtype)
                x = nn.Conv(dim, (2, 2), strides=(2, 2), dtype=dtype,
                            name=f"down_conv_{s}")(x)
            for i in range(depth):
                x = ConvNextLayer(dim, cfg.layer_scale, dtype,
                                  name=f"stage{s}_layer{i}")(x)
            f = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                             name=f"out_norm_{s}")(x).astype(dtype)
            features.append(f)

        # ---- PSP over the last feature
        last = features[-1]
        size = last.shape[1:3]
        psp = [last]
        for k, scale in enumerate(cfg.pool_scales):
            p = adaptive_avg_pool(last, scale)
            p = BNConv(cfg.channels, 1, dtype, name=f"psp_{k}")(p)
            psp.append(resize_bilinear(p, size))
        lat_last = BNConv(cfg.channels, 3, dtype, name="bottleneck")(
            jnp.concatenate(psp, axis=-1))

        # ---- FPN top-down
        laterals = [BNConv(cfg.channels, 1, dtype, name=f"lateral_{i}")(
            features[i]) for i in range(len(features) - 1)]
        laterals.append(lat_last)
        for i in range(len(laterals) - 1, 0, -1):
            laterals[i - 1] = laterals[i - 1] + resize_bilinear(
                laterals[i], laterals[i - 1].shape[1:3])
        outs = [BNConv(cfg.channels, 3, dtype, name=f"fpn_{i}")(
            laterals[i]) for i in range(len(laterals) - 1)]
        outs.append(laterals[-1])
        target = outs[0].shape[1:3]
        outs = [resize_bilinear(o, target) for o in outs]
        fused = BNConv(cfg.channels, 3, dtype, name="fpn_bottleneck")(
            jnp.concatenate(outs, axis=-1))
        logits = nn.Conv(cfg.num_labels, (1, 1), dtype=jnp.float32,
                         name="classifier")(fused)
        # HF upsamples logits to the INPUT size, not a fixed canvas —
        # caught by the published-config oracle run at a non-canvas input
        logits = resize_bilinear(logits, pixel_values.shape[1:3])
        return jnp.argmax(logits, axis=-1).astype(jnp.uint8)


@dataclasses.dataclass
class UperNetDetector:
    """Host wrapper: resize/normalize to the canvas, run the jitted
    model, map class ids through the ADE palette."""

    params: dict
    config: UperNetConfig = UPERNET_CONVNEXT_SMALL

    def __post_init__(self) -> None:
        self._net = UperNetSeg(self.config)
        self._fwd = jax.jit(lambda p, x: self._net.apply(p, x))

    @classmethod
    def random(cls, seed: int = 0,
               config: UperNetConfig = UPERNET_TINY) -> "UperNetDetector":
        net = UperNetSeg(config)
        x = jnp.zeros((1, config.image_size, config.image_size, 3),
                      jnp.float32)
        return cls(params=jax.jit(net.init)(jax.random.PRNGKey(seed), x),
                   config=config)

    @classmethod
    def from_checkpoint(cls, path,
                        config: UperNetConfig = UPERNET_CONVNEXT_SMALL,
                        ) -> "UperNetDetector":
        from chiaswarm_tpu.convert.torch_to_flax import (
            convert_upernet,
            read_torch_weights,
        )

        return cls(params=convert_upernet(read_torch_weights(path)),
                   config=config)

    def class_map(self, image: np.ndarray) -> np.ndarray:
        import cv2

        h, w = image.shape[:2]
        s = self.config.image_size
        resized = cv2.resize(image, (s, s), interpolation=cv2.INTER_CUBIC)
        arr = resized.astype(np.float32) / 255.0
        # ImageNet normalization (the UperNet image processor)
        mean = np.asarray([0.485, 0.456, 0.406], np.float32)
        std = np.asarray([0.229, 0.224, 0.225], np.float32)
        arr = (arr - mean) / std
        out = np.asarray(self._fwd(self.params, jnp.asarray(arr)[None]))[0]
        return cv2.resize(out, (w, h), interpolation=cv2.INTER_NEAREST)

    def __call__(self, image: np.ndarray) -> np.ndarray:
        """uint8 RGB -> uint8 RGB ADE-colored segmentation map."""
        from chiaswarm_tpu.models.ade_palette import ADE20K_PALETTE

        classes = self.class_map(image)
        # class k -> palette row k, exactly the reference's mapping
        # (input_processor.py:109-113; row 0 is black)
        idx = np.minimum(classes.astype(np.int32),
                         len(ADE20K_PALETTE) - 1)
        return ADE20K_PALETTE[idx]
