"""DeepSeek-V2-class decoder: latent attention (MLA) in every layer with
a low-rank query and YaRN-scaled rotary frequencies, softmax-routed
sparse experts held in part beside two shared experts — the second
language model behind the ``txt2txt`` workflow (pipelines/text.py).

Composed from ``models/text_layers.py``, which it shares with
models/ling.py: the latent-attention core (cache entry, up-projected
causal prefill, absorbed decode against a shared prompt and per-row
suffixes) and the held-experts layer. This stack's own:

- **the query** goes through a bottleneck: ``c_q = RMSNorm(W_dq x)``
  (``q_lora_rank`` wide), then ``W_uq c_q`` to the heads' nope and rope
  parts;
- **YaRN** (``text_layers.yarn_band`` / ``yarn_frequencies`` /
  ``yarn_mscale``, which the Laguna stack calls too; this module keeps
  the readings of its own ``rope_scaling`` group): of the rotary
  frequencies ``f_i = theta^(-2i/d)`` the fast
  ones (more than ``beta_fast`` turns over the original context) stay,
  the slow ones (fewer than ``beta_slow``) are divided by ``factor``,
  those between are blended linearly in i; the softmax scale is
  ``(nope + rope)^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``, and cos and sin are scaled by the ratio of the two
  mscales (1 for the published keys);
- **no gate** after the heads' read-out: W_o alone;
- **the router**: softmax over ALL routed experts in float32; a group's
  score is the largest of its experts'; the best ``topk_group`` groups
  stay; the best ``num_experts_per_tok`` of what is left are chosen
  (``group_limited_greedy``); their weights are their probabilities,
  not normalised, times ``routed_scaling_factor``;
- **the shared experts** are one SwiGLU of width ``n_shared_experts x
  moe_intermediate_size``; layer 0 (``first_k_dense_replace``) has a
  dense SwiGLU in place of experts.

The only cache is the latent one: ``kv_lora_rank + qk_rope_head_dim``
values a token a layer, whatever the head count.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from chiaswarm_tpu.models import text_layers
from chiaswarm_tpu.models.text_layers import (  # noqa: F401
    HIGHEST,
    empty_stats,
    param_bytes,
    proj,
    rms_norm,
)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """The config's ``rope_scaling`` group (``type`` "yarn")."""

    factor: float = 40.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    original_max_position_embeddings: int = 4096


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    """Sizes by their ``config.json`` names. ``vocab_size`` and
    ``experts_held`` are what THIS chip holds: the router keeps
    ``n_routed_experts`` outputs whatever is held."""

    vocab_size: int = 102400
    hidden_size: int = 5120
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: YarnScaling = YarnScaling()
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 2
    n_routed_experts: int = 160
    experts_held: tuple[int, int] = (0, 160)    # [first, past the last)
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    #: the module that serves this configuration (models/text_stacks.py)
    stack: ClassVar[str] = "deepseek"

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


#: the CPU tests' size: one dense layer and four expert layers, 16
#: experts in 4 groups (2 kept, 3 chosen) of which 4 are held, a query
#: bottleneck of 24, and a YaRN with every case in its 4 frequency
#: pairs (pairs 0 and 1 kept, pair 2 blended by half, pair 3 divided by
#: the factor) and two mscales that differ (cos and sin times 1.037)
TINY = DeepseekConfig(
    vocab_size=96, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_scaling=YarnScaling(factor=4.0, beta_fast=2.0, beta_slow=0.05,
                             mscale=1.0, mscale_all_dim=0.707,
                             original_max_position_embeddings=256),
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
    experts_held=(0, 4), num_experts_per_tok=3, n_group=4, topk_group=2,
    dtype="float32")


# ---- YaRN (models/text_layers.py computes it; these read the config) ------

yarn_mscale = text_layers.yarn_mscale


def _yarn_keys(cfg: DeepseekConfig) -> tuple:
    y = cfg.rope_scaling
    return (cfg.qk_rope_head_dim, cfg.rope_theta,
            y.original_max_position_embeddings, y.beta_fast, y.beta_slow)


def yarn_band(cfg: DeepseekConfig) -> tuple[int, int]:
    """(low, high) over the ``qk_rope_head_dim`` rotated values."""
    return text_layers.yarn_band(*_yarn_keys(cfg))


def yarn_frequencies(cfg: DeepseekConfig) -> np.ndarray:
    """The ``qk_rope_head_dim // 2`` rotary frequencies, float32."""
    dim, theta, *rest = _yarn_keys(cfg)
    return text_layers.yarn_frequencies(dim, theta, cfg.rope_scaling.factor,
                                        *rest)


def rope_amplitude(cfg: DeepseekConfig) -> float:
    """What cos and sin are scaled by: the ratio of the two mscales."""
    y = cfg.rope_scaling
    return yarn_mscale(y.factor, y.mscale) \
        / yarn_mscale(y.factor, y.mscale_all_dim)


def softmax_scale(cfg: DeepseekConfig) -> float:
    m = yarn_mscale(cfg.rope_scaling.factor,
                    cfg.rope_scaling.mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


# ---- checkpoint layout -----------------------------------------------------


def param_shapes(cfg: DeepseekConfig) -> dict[str, Any]:
    """The checkpoint's layout as a pytree of ShapeDtypeStruct: what a
    converter (or the benchmark's seeded fill) has to produce."""
    dt = jnp.dtype(cfg.dtype)
    d, h = cfg.hidden_size, cfg.num_attention_heads
    held = text_layers.n_held(cfg)

    def w(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def swiglu(width, lead=()):
        return {"gate": w(*lead, d, width), "up": w(*lead, d, width),
                "down": w(*lead, width, d)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        attn = {
            "wdq": w(d, cfg.q_lora_rank), "q_norm": w(cfg.q_lora_rank),
            "wuq": w(cfg.q_lora_rank,
                     h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
            "wdkv": w(d, text_layers.latent_width(cfg)),
            "kv_norm": w(cfg.kv_lora_rank),
            "wukv": w(cfg.kv_lora_rank,
                      h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": w(h * cfg.v_head_dim, d)}
        if cfg.is_moe(i):
            mlp = {"router": w(d, cfg.n_routed_experts, dtype=jnp.float32),
                   "experts": swiglu(cfg.moe_intermediate_size, (held,)),
                   "shared": swiglu(cfg.n_shared_experts
                                    * cfg.moe_intermediate_size)}
        else:
            mlp = swiglu(cfg.intermediate_size)
        layers.append({"attn_norm": w(d), "attn": attn,
                       "mlp_norm": w(d), "mlp": mlp})
    return {"embed": w(cfg.vocab_size, d), "layers": layers,
            "final_norm": w(d), "head": w(d, cfg.vocab_size)}


def random_params(cfg: DeepseekConfig, seed: int = 0) -> dict[str, Any]:
    """Host-side random weights for tiny presets (tests, the registry's
    ``allow_random``): projections fan-in scaled, norm gains one."""
    return text_layers.random_fill(param_shapes(cfg), seed)


# ---- the layers ------------------------------------------------------------


def _query(p, cfg: DeepseekConfig, x):
    """W_uq RMSNorm(W_dq x): (B, T, H * (nope + rope))."""
    c_q = rms_norm(proj(x, p["wdq"]), p["q_norm"], cfg.rms_norm_eps)
    return proj(c_q, p["wuq"])


def _told(cfg: DeepseekConfig) -> dict[str, Any]:
    """What this stack tells the shared latent-attention core."""
    return {"inv_freq": jnp.asarray(yarn_frequencies(cfg)),
            "scale": softmax_scale(cfg),
            "rope_amplitude": rope_amplitude(cfg)}


def _out(p, x, o):
    b_, t = x.shape[:2]
    return proj(o.reshape(b_, t, -1), p["wo"])


def mla_prefill(p, cfg: DeepseekConfig, x, cache, pos):
    """x (B, T, d) at positions [pos, pos + T) -> (y, cache)."""
    o, cache = text_layers.latent_prefill(
        p, cfg, x, _query(p, cfg, x), cache, pos, **_told(cfg))
    return _out(p, x, o), cache


def mla_decode(p, cfg: DeepseekConfig, x, prompt_cache, prompt_len, suffix,
               step):
    """One new token a row, absorbed form: x (R, 1, d) -> (y, suffix)."""
    o, suffix = text_layers.latent_decode(
        p, cfg, x, _query(p, cfg, x), prompt_cache, prompt_len, suffix,
        step, **_told(cfg))
    return _out(p, x, o), suffix


def route(p, cfg: DeepseekConfig, x):
    """x (T, d) -> (chosen experts (T, K) int32, their weights (T, K)
    float32): softmax over ALL experts; the best ``topk_group`` groups
    by their largest probability, then the best K inside them; weights
    are the probabilities of the chosen as they are, times the scaling
    factor."""
    t = x.shape[0]
    scores = jax.nn.softmax(jnp.dot(x.astype(jnp.float32), p["router"],
                                    precision=HIGHEST), axis=-1)
    groups = scores.reshape(t, cfg.n_group, -1)
    _, best = jax.lax.top_k(jnp.max(groups, axis=-1), cfg.topk_group)
    keep = jnp.zeros((t, cfg.n_group), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    masked = jnp.where(keep[..., None], groups, 0.0).reshape(t, -1)
    weight, chosen = jax.lax.top_k(masked, cfg.num_experts_per_tok)
    return chosen.astype(jnp.int32), weight * cfg.routed_scaling_factor


def moe(p, cfg: DeepseekConfig, x, valid=None):
    """x (..., d) -> (shared experts + held experts' part, stats)."""
    return text_layers.moe(p, cfg, x, route, valid)


# ---- the stack -------------------------------------------------------------


def empty_prefill_caches(cfg: DeepseekConfig, capacity: int):
    """One row's caches before its first token: a latent cache of
    ``capacity`` entries per layer."""
    return {"mla": text_layers.empty_latent_caches(
        cfg, cfg.num_hidden_layers, capacity)}


def prefill_chunk(params, cfg: DeepseekConfig, ids, caches, pos, n_valid):
    """One chunk of one row: ids (1, T) at positions [pos, pos + T), of
    which the first ``n_valid`` are tokens (padding past them writes
    entries that the next chunk overwrites or that no query sees).
    Returns (logits after the last valid token (1, V), caches, expert
    stats)."""
    x = params["embed"][ids]
    valid = jnp.arange(ids.shape[1]) < n_valid
    mla = list(caches["mla"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        y, mla[i] = mla_prefill(layer["attn"], cfg, h, mla[i], pos)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route,
                                         valid[None])
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)[:, 0]
    return text_layers.logits_of(params, cfg, last), {"mla": mla}, stats


def decode_caches(cfg: DeepseekConfig, caches, rows: int, max_new: int):
    """The prompt's latents shared by ``rows`` rows, an empty suffix of
    ``max_new`` latents a row."""
    return {"prompt": caches["mla"],
            "suffix": text_layers.empty_suffixes(cfg, caches["mla"], rows,
                                                 max_new)}


def decode_step(params, cfg: DeepseekConfig, tokens, caches, prompt_len,
                step):
    """One new token a row: tokens (R,) at position prompt_len + step.
    Returns (logits (R, V), caches, expert stats)."""
    x = params["embed"][tokens][:, None]
    suffix = list(caches["suffix"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        y, suffix[i] = mla_decode(layer["attn"], cfg, h,
                                  caches["prompt"][i], prompt_len,
                                  suffix[i], step)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route)
    caches = {"prompt": caches["prompt"], "suffix": suffix}
    return text_layers.logits_of(params, cfg, x[:, 0]), caches, stats


def cache_bytes(cfg: DeepseekConfig, rows: int, capacity: int,
                max_new: int) -> dict[str, int]:
    """Bytes of the one kind of cache a decode of ``rows`` rows holds."""
    return {"latent": text_layers.latent_cache_bytes(
        cfg, cfg.num_hidden_layers, rows, capacity, max_new)}


def job_counts(cfg: DeepseekConfig, prompt_tokens: int, rows: int, new: int,
               chunk: int, capacity: int) -> dict[str, Any]:
    """What the host knows of one job's two programs, for the counters
    (``pipelines/text.py::TextPipeline._count``): key blocks the causal
    kernel reads and leaves in the prefill and in the decode, query-key
    pairs a head scores by phase, the prefill kernel's grid steps by
    kind, the expert layers."""
    layers = cfg.num_hidden_layers
    return {
        "key_blocks": text_layers.prefill_key_blocks(
            layers, prompt_tokens, chunk, capacity),
        "block_steps": text_layers.prefill_block_steps(
            layers, cfg.num_attention_heads, 1, prompt_tokens, chunk,
            capacity),
        "decode_key_blocks": text_layers.decode_key_blocks(
            layers, prompt_tokens, new, capacity),
        "attention_pairs": text_layers.attention_pairs(
            layers, prompt_tokens, rows, new),
        "expert_layers": sum(cfg.is_moe(i) for i in range(layers))}
