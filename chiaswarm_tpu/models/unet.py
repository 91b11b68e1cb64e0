"""Conditional diffusion UNet (Flax, NHWC) for SD 1.x / 2.x / SDXL families.

This is the hot-loop model of the whole framework — the denoise step the
reference runs inside ``pipeline(**kwargs)`` (swarm/diffusion/
diffusion_func.py:96) spends ~all its FLOPs here. TPU-first choices:

- NHWC layout throughout (XLA:TPU's native conv layout; channels ride the
  128-lane dimension).
- Attention runs through chiaswarm_tpu.ops.attention — spatial self-attention
  dispatches to the Pallas flash kernel on TPU, text cross-attention stays on
  the fused-einsum path (tiny KV).
- Fractional timesteps supported (Karras-sigma conditioning interpolates the
  timestep table — schedulers/common.py:sigma_to_timestep).
- No Python control flow on traced values; the module is shape-static and
  jits into one executable per (batch, resolution) bucket.

Covers: SD1.5 (head-count attention, conv projections), SD2.1 (head-dim 64,
linear projections, v-prediction handled by the scheduler), SDXL (mixed
transformer depth [1,2,10], dual-text conditioning + pooled/time-id
micro-conditioning embeddings).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from chiaswarm_tpu.models.configs import UNetConfig
from chiaswarm_tpu.models.common import num_groups as _num_groups
from chiaswarm_tpu.models.common import upsample2x_nearest
from chiaswarm_tpu.obs.trace import scope
from chiaswarm_tpu.ops.attention import attention


def timestep_embedding(timesteps: jnp.ndarray, dim: int,
                       flip_sin_to_cos: bool = True,
                       freq_shift: float = 0.0,
                       max_period: float = 10000.0) -> jnp.ndarray:
    """Sinusoidal embedding, (B,) -> (B, dim). fp32 regardless of model dtype."""
    half = dim // 2
    freqs = jnp.exp(
        -jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32)
        / (half - freq_shift)
    )
    args = timesteps.astype(jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(args), jnp.cos(args)
    emb = jnp.concatenate([cos, sin], axis=-1) if flip_sin_to_cos else \
        jnp.concatenate([sin, cos], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


class TimestepEmbedding(nn.Module):
    """Two-layer MLP lifting the sinusoidal embedding to the block width.
    ``hidden_dim`` covers diffusers' ``out_dim`` variant (SVD's
    ``time_pos_embed``: C -> 4C -> C); None keeps both layers at
    ``out_dim``."""

    out_dim: int
    dtype: jnp.dtype = jnp.float32
    hidden_dim: int | None = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = nn.Dense(self.hidden_dim or self.out_dim, dtype=self.dtype,
                     name="linear_1")(x)
        x = nn.silu(x)
        return nn.Dense(self.out_dim, dtype=self.dtype, name="linear_2")(x)


class ResnetBlock(nn.Module):
    out_channels: int
    dtype: jnp.dtype = jnp.float32
    eps: float = 1e-5  # SVD's attention-level blocks ship 1e-6

    @nn.compact
    @scope("resnet")
    def __call__(self, x: jnp.ndarray, temb: jnp.ndarray) -> jnp.ndarray:
        h = nn.GroupNorm(num_groups=_num_groups(x.shape[-1]), epsilon=self.eps, dtype=jnp.float32,
                         name="norm1")(x)
        h = nn.silu(h).astype(self.dtype)
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="conv1")(h)
        t = nn.Dense(self.out_channels, dtype=self.dtype,
                     name="time_emb_proj")(nn.silu(temb))
        h = h + t[:, None, None, :]
        h = nn.GroupNorm(num_groups=_num_groups(h.shape[-1]), epsilon=self.eps, dtype=jnp.float32,
                         name="norm2")(h)
        h = nn.silu(h).astype(self.dtype)
        h = nn.Conv(self.out_channels, (3, 3), padding=1, dtype=self.dtype,
                    name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype,
                        name="conv_shortcut")(x)
        return x + h


class FeedForward(nn.Module):
    """GEGLU feed-forward (transformer MLP used by SD's attention blocks)."""

    dim: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        inner = self.dim * 4
        x = nn.Dense(inner * 2, dtype=self.dtype, name="proj_in")(x)
        x, gate = jnp.split(x, 2, axis=-1)
        # exact (erf) gelu — diffusers' GEGLU calls F.gelu without the
        # tanh approximation; matters for number-level checkpoint parity
        x = x * nn.gelu(gate, approximate=False)
        return nn.Dense(self.dim, dtype=self.dtype, name="proj_out")(x)


class CrossAttention(nn.Module):
    """Scaled dot-product attention with to_q/to_k/to_v/to_out heads.

    Context-batch contract: ``context.shape[0]`` must equal the query
    batch OR divide it, and in the divisible case query rows must be
    ordered context-major (row ``i`` attends to ``context[i // m]`` for
    ``m = b // bc`` — what ``(B, F, ...) -> (B*F, ...)`` folds and
    ``(b, s, f, c) -> (b*s, f, c)`` reshapes produce). CFG callers must
    still concatenate their negative/positive contexts to the full query
    batch themselves: a batch-1 context against a CFG pair would be
    silently broadcast to both halves, making guidance a no-op.
    """

    num_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x: jnp.ndarray, context: jnp.ndarray | None) -> jnp.ndarray:
        context = x if context is None else context
        inner = self.num_heads * self.head_dim
        b, l, _ = x.shape
        bc, s = context.shape[:2]
        q = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_q")(x)
        k = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_k")(context)
        v = nn.Dense(inner, use_bias=False, dtype=self.dtype, name="to_v")(context)
        if s == 1:
            # Softmax over a single key is identically 1, so the attended
            # value is the value row itself — independent of the queries.
            # out == to_out(v) broadcast over every query position (exact,
            # not an approximation; SVD's one-token CLIP-image context hits
            # this in every spatial and temporal cross-attention). q/k above
            # are kept so the param tree matches checkpoints; XLA removes
            # the dead computation. The context batch may be a divisor of
            # the query batch (an unbroadcast per-sample token): the result
            # broadcast replaces materializing the per-site context.
            out = nn.Dense(inner, dtype=self.dtype, name="to_out")(v)
            out = jnp.broadcast_to(out.reshape(bc, 1, 1, inner),
                                   (bc, b // bc, l, inner))
            return out.reshape(b, l, inner)
        if bc != b:
            # un-broadcast per-sample context on the general path too, so
            # callers never depend on which path runs: expand k/v after
            # projection (cheaper than materializing a per-site context)
            k = jnp.broadcast_to(k[:, None], (bc, b // bc, s, inner))
            v = jnp.broadcast_to(v[:, None], (bc, b // bc, s, inner))
            k = k.reshape(b, s, inner)
            v = v.reshape(b, s, inner)
        q = q.reshape(b, l, self.num_heads, self.head_dim)
        k = k.reshape(b, s, self.num_heads, self.head_dim)
        v = v.reshape(b, s, self.num_heads, self.head_dim)
        out = attention(q, k, v, impl=self.attn_impl).reshape(b, l, inner)
        return nn.Dense(inner, dtype=self.dtype, name="to_out")(out)


class TransformerBlock(nn.Module):
    num_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"
    has_cross_attn: bool = True

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 context: jnp.ndarray | None) -> jnp.ndarray:
        # spatial self-attention (flash-kernel eligible)
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm1")(x).astype(self.dtype)
        x = x + CrossAttention(self.num_heads, self.head_dim, self.dtype,
                               self.attn_impl, name="attn1")(h, None)
        if self.has_cross_attn:
            # text cross-attention (small KV -> einsum path)
            h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm2")(x).astype(self.dtype)
            x = x + CrossAttention(self.num_heads, self.head_dim, self.dtype,
                                   "xla", name="attn2")(h, context)
        h = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm3")(x).astype(self.dtype)
        return x + FeedForward(x.shape[-1], self.dtype, name="ff")(h)


class SpatialTransformer(nn.Module):
    """GroupNorm -> project -> depth x TransformerBlock -> project + residual."""

    depth: int
    num_heads: int
    head_dim: int
    use_linear_projection: bool
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"
    has_cross_attn: bool = True

    @nn.compact
    @scope("transformer")
    def __call__(self, x: jnp.ndarray,
                 context: jnp.ndarray | None) -> jnp.ndarray:
        b, h, w, c = x.shape
        residual = x
        x = nn.GroupNorm(num_groups=_num_groups(x.shape[-1]), epsilon=1e-6, dtype=jnp.float32,
                         name="norm")(x).astype(self.dtype)
        if self.use_linear_projection:
            x = x.reshape(b, h * w, c)
            x = nn.Dense(c, dtype=self.dtype, name="proj_in")(x)
        else:
            x = nn.Conv(c, (1, 1), dtype=self.dtype, name="proj_in")(x)
            x = x.reshape(b, h * w, c)
        for i in range(self.depth):
            x = TransformerBlock(self.num_heads, self.head_dim, self.dtype,
                                 self.attn_impl, self.has_cross_attn,
                                 name=f"transformer_blocks_{i}")(x, context)
        if self.use_linear_projection:
            x = nn.Dense(c, dtype=self.dtype, name="proj_out")(x)
            x = x.reshape(b, h, w, c)
        else:
            x = x.reshape(b, h, w, c)
            x = nn.Conv(c, (1, 1), dtype=self.dtype, name="proj_out")(x)
        return x + residual


class Downsample(nn.Module):
    channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return nn.Conv(self.channels, (3, 3), strides=(2, 2), padding=1,
                       dtype=self.dtype, name="conv")(x)


class Upsample(nn.Module):
    channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = upsample2x_nearest(x)
        return nn.Conv(self.channels, (3, 3), padding=1, dtype=self.dtype,
                       name="conv")(x)


def time_conditioning(cfg: UNetConfig, dtype: jnp.dtype,
                      timesteps: jnp.ndarray,
                      added_cond: dict[str, jnp.ndarray] | None,
                      class_labels: jnp.ndarray | None = None) -> jnp.ndarray:
    """Timestep (+ SDXL micro-conditioning, + class-label) embedding.
    Shared by the UNet and the ControlNet trunk — creates the
    ``time_embedding`` / ``add_embedding`` / ``class_embedding`` submodules
    in the CALLER's compact scope, so both models keep identical parameter
    paths for the checkpoint converter."""
    channels = list(cfg.block_out_channels)
    time_embed_dim = channels[0] * 4
    temb = timestep_embedding(timesteps, channels[0],
                              cfg.flip_sin_to_cos, cfg.freq_shift)
    temb = TimestepEmbedding(time_embed_dim, dtype=dtype,
                             name="time_embedding")(temb.astype(dtype))
    if cfg.num_class_embeds is not None:
        # noise-level conditioning (SD-x4-upscaler): a learned embedding
        # row per discrete level, added to the time embedding
        if class_labels is None:
            raise ValueError("this family requires class_labels "
                             "(e.g. the x4-upscaler noise level)")
        temb = temb + nn.Embed(cfg.num_class_embeds, time_embed_dim,
                               dtype=dtype, name="class_embedding")(
            class_labels.astype(jnp.int32))
    if cfg.class_proj_dim is not None:
        # FiLM conditioning on a continuous vector (AudioLDM conditions the
        # UNet on the L2-normalized CLAP text_embeds this way — diffusers'
        # class_embed_type="simple_projection"); class_labels is (B, D) float
        if class_labels is None:
            raise ValueError("this family requires float class_labels "
                             "(e.g. AudioLDM's projected text embedding)")
        class_emb = nn.Dense(time_embed_dim, dtype=dtype,
                             name="class_embedding")(
            class_labels.astype(dtype))
        if cfg.class_embeddings_concat:
            temb = jnp.concatenate([temb, class_emb], axis=-1)
        else:
            temb = temb + class_emb
    if cfg.addition_embed_dim is not None:
        if added_cond is None:
            raise ValueError("this family requires added_cond "
                             "(time_ids [+ text_embeds])")
        # SDXL: 6 time ids + pooled text; SVD-class video: 3 ids
        # (fps, motion bucket, noise-aug strength), no pooled branch
        time_ids = added_cond["time_ids"]          # (B, K)
        b = time_ids.shape[0]
        ids_emb = timestep_embedding(
            time_ids.reshape(-1), cfg.addition_embed_dim,
            cfg.flip_sin_to_cos, cfg.freq_shift,
        ).reshape(b, -1)
        if cfg.addition_pooled_dim is not None:
            text_embeds = added_cond["text_embeds"]  # (B, pooled_dim)
            add = jnp.concatenate(
                [text_embeds.astype(jnp.float32), ids_emb], axis=-1)
        else:
            add = ids_emb
        temb = temb + TimestepEmbedding(
            time_embed_dim, dtype=dtype, name="add_embedding"
        )(add.astype(dtype))
    return temb


def down_trunk(cfg: UNetConfig, dtype: jnp.dtype, x: jnp.ndarray,
               temb: jnp.ndarray, context: jnp.ndarray,
               ) -> tuple[jnp.ndarray, list[jnp.ndarray]]:
    """Down path from the post-conv_in activation: returns (x, skips).
    Shared verbatim by UNet and ControlNet (same submodule names)."""
    channels = list(cfg.block_out_channels)
    skips = [x]
    for level, ch in enumerate(channels):
        depth = cfg.transformer_depth[level]
        heads, head_dim = cfg.heads_for(ch, level)
        for j in range(cfg.layers_per_block):
            x = ResnetBlock(ch, dtype,
                            name=f"down_{level}_resnets_{j}")(x, temb)
            if depth > 0:
                x = SpatialTransformer(
                    depth, heads, head_dim, cfg.use_linear_projection,
                    dtype, cfg.attn_impl,
                    cfg.cross_attention_dim is not None,
                    name=f"down_{level}_attentions_{j}",
                )(x, context)
            skips.append(x)
        if level < len(channels) - 1:
            x = Downsample(ch, dtype, name=f"down_{level}_downsample")(x)
            skips.append(x)
    return x, skips


def mid_trunk(cfg: UNetConfig, dtype: jnp.dtype, x: jnp.ndarray,
              temb: jnp.ndarray, context: jnp.ndarray) -> jnp.ndarray:
    """Mid block (resnet -> transformer -> resnet), shared like down_trunk."""
    channels = list(cfg.block_out_channels)
    mid_ch = channels[-1]
    mid_heads, mid_head_dim = cfg.heads_for(mid_ch, len(channels) - 1)
    mid_depth = max(d for d in cfg.transformer_depth) or 1
    x = ResnetBlock(mid_ch, dtype, name="mid_resnets_0")(x, temb)
    x = SpatialTransformer(mid_depth, mid_heads, mid_head_dim,
                           cfg.use_linear_projection, dtype,
                           cfg.attn_impl,
                           cfg.cross_attention_dim is not None,
                           name="mid_attention")(x, context)
    return ResnetBlock(mid_ch, dtype, name="mid_resnets_1")(x, temb)


def self_attention_sites(cfg: UNetConfig, height: int, width: int, *,
                         part: str = "full") -> dict[tuple[int, int, int],
                                                     int]:
    """The spatial self-attention calls of one pass over a (height,
    width) latent, by (tokens, heads, head size): host integers of the
    blocks ``down_trunk``, ``mid_trunk`` and the up path build. ``part``:
    ``"full"``, the whole network; ``"shallow"``, the DeepCache replay
    (level 0's down and up blocks); ``"trunk"``, the down path and the
    mid block (ControlNet's share)."""
    channels = list(cfg.block_out_channels)
    up_blocks = 0 if part == "trunk" else cfg.layers_per_block + 1
    sites: dict[tuple[int, int, int], int] = {}

    def add(tokens: int, ch: int, level: int, calls: int) -> None:
        key = (tokens, *cfg.heads_for(ch, level))
        sites[key] = sites.get(key, 0) + calls

    h, w = height, width
    for level, ch in enumerate(channels):
        depth = cfg.transformer_depth[level]
        if depth and (level == 0 or part != "shallow"):
            add(h * w, ch, level,
                depth * (cfg.layers_per_block + up_blocks))
        if level < len(channels) - 1:
            h, w = -(-h // 2), -(-w // 2)      # 3x3, stride 2, padding 1
    if part != "shallow":
        add(h * w, channels[-1], len(channels) - 1,
            max(cfg.transformer_depth) or 1)
    return sites


class UNet(nn.Module):
    """Returns the model prediction (epsilon/v per family) for NHWC latents.

    ``down_residuals``/``mid_residual`` inputs accept ControlNet residual
    injections (models/controlnet.py) — ``None`` for plain generation.

    DeepCache seam (ISSUE 12, Ma et al. 2023): adjacent denoise steps
    share slow-changing DEEP features, so the step-collapse subsystem
    (pipelines/diffusion.py) caches the up-path activation entering the
    shallowest (level 0) up block and replays it on designated steps:

    - ``return_deep=True`` runs the full network and ALSO returns that
      activation — the cache-refresh step. Static flag: the default
      trace is byte-identical to the pre-seam network.
    - ``cached_deep`` (the captured activation) runs the SHALLOW replay:
      conv_in + the level-0 down blocks recompute (they feed the level-0
      skip connections), every deeper level, the mid block, and the
      deeper up path are SKIPPED, and the cached activation splices in
      where the level-1 upsample output would arrive. For SDXL that
      skips the transformer-heavy levels entirely — the dominant cost
      of a denoise step.

    Both variants keep the exact submodule names of the full path, so
    one parameter tree serves all three traces.
    """

    config: UNetConfig

    @property
    def dtype(self) -> jnp.dtype:
        return jnp.dtype(self.config.dtype)

    @nn.compact
    def __call__(
        self,
        sample: jnp.ndarray,               # (B, H, W, C_latent)
        timesteps: jnp.ndarray,            # (B,) float32 (fractional ok)
        encoder_hidden_states: jnp.ndarray | None,  # (B, S, cross_dim);
        #   None for families without text cross-attention (AudioLDM)
        added_cond: dict[str, jnp.ndarray] | None = None,  # SDXL micro-cond
        down_residuals: tuple[jnp.ndarray, ...] | None = None,
        mid_residual: jnp.ndarray | None = None,
        # (B,) int noise level (x4-upscaler) or (B, class_proj_dim) float
        # FiLM vector (AudioLDM text_embeds)
        class_labels: jnp.ndarray | None = None,
        # DeepCache seam (static at trace time; see class docstring)
        cached_deep: jnp.ndarray | None = None,
        return_deep: bool = False,
    ) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.config
        dtype = self.dtype
        channels = list(cfg.block_out_channels)
        if (cached_deep is not None or return_deep) and len(channels) < 2:
            raise ValueError(
                "DeepCache needs a deep/shallow split: this UNet has a "
                "single resolution level")
        if cached_deep is not None and return_deep:
            raise ValueError("shallow replay already carries the cache; "
                             "return_deep only applies to full passes")

        temb = time_conditioning(cfg, dtype, timesteps, added_cond,
                                 class_labels)
        context = (None if encoder_hidden_states is None
                   else encoder_hidden_states.astype(dtype))
        sample = sample.astype(dtype)

        x = nn.Conv(channels[0], (3, 3), padding=1, dtype=dtype,
                    name="conv_in")(sample)

        if cached_deep is not None:
            # ---- shallow replay: level-0 down blocks only (they feed
            # the level-0 skips), then the cached deep activation stands
            # in for the whole level>=1 + mid + deeper-up computation
            ch0 = channels[0]
            depth0 = cfg.transformer_depth[0]
            heads0, head_dim0 = cfg.heads_for(ch0, 0)
            skips = [x]
            for j in range(cfg.layers_per_block):
                x = ResnetBlock(ch0, dtype,
                                name=f"down_0_resnets_{j}")(x, temb)
                if depth0 > 0:
                    x = SpatialTransformer(
                        depth0, heads0, head_dim0,
                        cfg.use_linear_projection, dtype, cfg.attn_impl,
                        cfg.cross_attention_dim is not None,
                        name=f"down_0_attentions_{j}",
                    )(x, context)
                skips.append(x)
            if down_residuals is not None:
                # only the level-0 residuals have matching skips here
                skips = [s + r for s, r in zip(skips, down_residuals)]
            x = cached_deep.astype(dtype)
            up_levels: list[int] = [0]
        else:
            x, skips = down_trunk(cfg, dtype, x, temb, context)

            if down_residuals is not None:
                skips = [s + r for s, r in zip(skips, down_residuals)]

            x = mid_trunk(cfg, dtype, x, temb, context)
            if mid_residual is not None:
                x = x + mid_residual
            up_levels = list(range(len(channels) - 1, -1, -1))

        # ---- up path (mirrors down, consumes skips)
        deep = None
        for level in up_levels:
            ch = channels[level]
            depth = cfg.transformer_depth[level]
            heads, head_dim = cfg.heads_for(ch, level)
            if level == 0 and return_deep:
                # the activation the shallow replay will splice back in:
                # the level-1 upsample output entering the level-0 blocks
                deep = x
            for j in range(cfg.layers_per_block + 1):
                skip = skips.pop()
                x = jnp.concatenate([x, skip], axis=-1)
                x = ResnetBlock(ch, dtype,
                                name=f"up_{level}_resnets_{j}")(x, temb)
                if depth > 0:
                    x = SpatialTransformer(
                        depth, heads, head_dim, cfg.use_linear_projection,
                        dtype, cfg.attn_impl,
                        cfg.cross_attention_dim is not None,
                        name=f"up_{level}_attentions_{j}",
                    )(x, context)
            if level > 0:
                x = Upsample(ch, dtype, name=f"up_{level}_upsample")(x)

        x = nn.GroupNorm(num_groups=_num_groups(x.shape[-1]), epsilon=1e-5, dtype=jnp.float32,
                         name="conv_norm_out")(x)
        x = nn.silu(x).astype(dtype)
        x = nn.Conv(cfg.out_channels, (3, 3), padding=1, dtype=jnp.float32,
                    name="conv_out")(x)
        if return_deep:
            return x, deep
        return x
