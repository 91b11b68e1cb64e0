"""Ling-3.0-flash-class decoder: delta-rule linear attention (KDA) beside
multi-head latent attention (MLA), sigmoid-routed sparse experts held in
part — the language model behind the ``txt2txt`` workflow
(pipelines/text.py, workloads/text.py).

Three layer kinds the UNet families do not have:

- **KDA** — a gated delta-rule recurrence with a per-channel decay: the
  core is ``models/text_layers.py``'s (chunked UT-form prefill, the
  recurrence in decode), shared with models/solar.py. This stack's own
  (``LingConfig.delta_rule``): the gate bounded below by
  ``kda_lower_bound`` (any gate in (e^-5, 1)), a write strength up to 1,
  full-rank projections of both gates.
- **MLA** — latent attention with a compressed cache (512 latent + 64
  rotary values a token): the core is ``models/text_layers.py``'s
  (up-projected causal prefill, absorbed decode), shared with
  models/deepseek.py. This stack's own: a full-rank query, plain rotary
  frequencies, ``(nope + rope)^-0.5`` as the softmax scale and one
  sigmoid gate a head before W_o.
- **Experts** — the held-experts layer of ``models/text_layers.py``
  under this stack's router: sigmoid scores with a bias, groups ranked
  by the sum of their top two, weights normalised over the chosen.

Functional style (a dict pytree of arrays, plain functions): the expert
weights are stacked (experts, in, out) and sliced by a traced index,
which flax modules would only obscure. Weights and activations in the
configuration's dtype (bfloat16 served), router and recurrent state in
float32 with ``Precision.HIGHEST`` (a float32 product is one bfloat16
pass on a TPU otherwise).
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from chiaswarm_tpu.models import text_layers
from chiaswarm_tpu.models.text_layers import (  # noqa: F401
    HIGHEST,
    empty_stats,
    kda_block,
    kda_chunked,
    kda_recurrent_step,
    param_bytes,
    rms_norm,
    swiglu,
)
from chiaswarm_tpu.models.text_layers import proj as _proj
from chiaswarm_tpu.models.text_layers import sigmoid_route as route
from chiaswarm_tpu.obs.trace import scope


@dataclasses.dataclass(frozen=True)
class LingConfig:
    """Sizes by their ``config.json`` names where one exists.
    ``vocab_size`` and ``experts_held`` are what THIS chip holds: the
    router keeps ``num_experts`` outputs whatever is held."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128                 # KDA d_k = d_v
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_experts: int = 512
    experts_held: tuple[int, int] = (0, 512)    # [first, past the last)
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    kda_chunk: int = 64                 # tokens a UT-form chunk holds

    #: the module that serves this configuration (models/text_stacks.py)
    stack: ClassVar[str] = "ling"

    def is_mla(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def kda_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if not self.is_mla(i)]

    @property
    def mla_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_mla(i)]

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def delta_rule(self) -> text_layers.DeltaRule:
        """What the KDA layers tell the delta-rule core: the bounded
        gate, a write strength up to 1."""
        return text_layers.DeltaRule(
            heads=self.num_attention_heads, head_dim=self.head_dim,
            conv=self.short_conv_kernel_size, chunk=self.kda_chunk,
            eps=self.rms_norm_eps, lower_bound=self.kda_lower_bound)


#: the CPU tests' size: 2 dense + one whole period, 16 experts in 4
#: groups of which 4 are held
LING_TINY = LingConfig(
    vocab_size=96, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=2, head_dim=16, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=16,
    experts_held=(0, 4), num_experts_per_tok=4, n_group=4, topk_group=2,
    dtype="float32", kda_chunk=4)
TINY = LING_TINY


# ---- checkpoint layout ---------------------------------------------------


def param_shapes(cfg: LingConfig) -> dict[str, Any]:
    """The checkpoint's layout as a pytree of ShapeDtypeStruct: what a
    converter (or the benchmark's seeded fill) has to produce."""
    dt = jnp.dtype(cfg.dtype)
    f32 = jnp.float32
    d, h = cfg.hidden_size, cfg.num_attention_heads
    inner = h * cfg.head_dim

    def w(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def swiglu(width, lead=()):
        return {"gate": w(*lead, d, width), "up": w(*lead, d, width),
                "down": w(*lead, width, d)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        if cfg.is_mla(i):
            attn = {
                "wq": w(d, h * (cfg.qk_nope_head_dim
                                + cfg.qk_rope_head_dim)),
                "wdkv": w(d, cfg.latent_width),
                "kv_norm": w(cfg.kv_lora_rank),
                "wukv": w(cfg.kv_lora_rank,
                          h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "wgate": w(d, h),
                "wo": w(h * cfg.v_head_dim, d)}
        else:
            attn = {
                "wq": w(d, inner), "wk": w(d, inner), "wv": w(d, inner),
                "conv_q": w(cfg.short_conv_kernel_size, inner),
                "conv_k": w(cfg.short_conv_kernel_size, inner),
                "conv_v": w(cfg.short_conv_kernel_size, inner),
                "wa": w(d, inner), "a_log": w(h, dtype=f32),
                "dt_bias": w(inner, dtype=f32), "wb": w(d, h),
                "wg": w(d, inner), "o_norm": w(cfg.head_dim),
                "wo": w(inner, d)}
        if cfg.is_moe(i):
            mlp = {"router": w(d, cfg.num_experts, dtype=f32),
                   "router_bias": w(cfg.num_experts, dtype=f32),
                   "experts": swiglu(cfg.moe_intermediate_size,
                                     (text_layers.n_held(cfg),)),
                   "shared": swiglu(cfg.moe_intermediate_size)}
        else:
            mlp = swiglu(cfg.intermediate_size)
        layers.append({"attn_norm": w(d), "attn": attn,
                       "mlp_norm": w(d), "mlp": mlp})
    return {"embed": w(cfg.vocab_size, d), "layers": layers,
            "final_norm": w(d), "head": w(d, cfg.vocab_size)}


def random_params(cfg: LingConfig, seed: int = 0) -> dict[str, Any]:
    """Host-side random weights for tiny presets (tests, the registry's
    ``allow_random``): projections fan-in scaled, norm gains one, the
    decay's bias set so that a channel forgets over tens of tokens."""
    import numpy as np

    rng = np.random.RandomState(seed)

    def fill(path, spec):
        name = path[-1].key
        if name.endswith("norm"):
            value = np.ones(spec.shape, np.float32)
        elif name == "dt_bias":
            value = rng.uniform(-5.0, -1.0, spec.shape)
        elif name in ("a_log", "router_bias"):
            value = rng.normal(0.0, 0.1, spec.shape)
        elif name.startswith("conv_"):
            value = rng.normal(0.0, 0.5, spec.shape)
        elif name == "embed":
            value = rng.normal(0.0, 1.0, spec.shape)
        else:  # (..., fan_in, fan_out)
            value = rng.normal(0.0, spec.shape[-2] ** -0.5, spec.shape)
        return jnp.asarray(value, spec.dtype)

    return jax.tree_util.tree_map_with_path(fill, param_shapes(cfg))


# ---- KDA: the delta-rule core is text_layers' ------------------------------


def kda_blocks(cfg: LingConfig, prompt_tokens: int, chunk: int
               ) -> tuple[int, int]:
    """``text_layers.kda_blocks`` over this stack's KDA layers."""
    return text_layers.kda_blocks(cfg.delta_rule, len(cfg.kda_layers),
                                  prompt_tokens, chunk)


# ---- MLA: latent attention (the core is text_layers') ---------------------


def _rope_frequencies(cfg: LingConfig):
    return text_layers.rope_frequencies(cfg.rope_theta,
                                        cfg.qk_rope_head_dim)


def _mla_out(p, cfg: LingConfig, x, o):
    """One sigmoid gate a head, then W_o. o (B, T, H, Dv)."""
    b_, t = x.shape[:2]
    gate = jax.nn.sigmoid(_proj(x, p["wgate"]).astype(jnp.float32))
    o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    return _proj(o.reshape(b_, t, -1), p["wo"])


def mla_scale(cfg: LingConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


@scope("attention")
def mla_prefill(p, cfg: LingConfig, x, cache, pos):
    """x (B, T, d) at positions [pos, pos + T) -> (y, cache): a
    full-rank query, ``text_layers.latent_prefill``, the gate, W_o."""
    o, cache = text_layers.latent_prefill(
        p, cfg, x, _proj(x, p["wq"]), cache, pos,
        inv_freq=_rope_frequencies(cfg), scale=mla_scale(cfg))
    return _mla_out(p, cfg, x, o), cache


@scope("attention")
def mla_decode(p, cfg: LingConfig, x, prompt_cache, prompt_len, suffix,
               step):
    """One new token a row, absorbed form (``text_layers.latent_decode``):
    x (R, 1, d) -> (y, suffix)."""
    o, suffix = text_layers.latent_decode(
        p, cfg, x, _proj(x, p["wq"]), prompt_cache, prompt_len, suffix,
        step, inv_freq=_rope_frequencies(cfg), scale=mla_scale(cfg))
    return _mla_out(p, cfg, x, o), suffix


# ---- experts (the layer and the router are text_layers') ------------------


def moe(p, cfg: LingConfig, x, valid=None):
    """x (..., d) -> (shared expert + held experts' part, stats)."""
    return text_layers.moe(p, cfg, x, route, valid)


# ---- the stack -------------------------------------------------------------


def empty_prefill_caches(cfg: LingConfig, capacity: int):
    """One row's caches before its first token: a recurrent state and
    conv tails per KDA layer, a latent cache of ``capacity`` entries per
    MLA layer."""
    return {"kda": [text_layers.kda_empty_cache(cfg.delta_rule, 1, cfg.dtype)
                    for _ in cfg.kda_layers],
            "mla": text_layers.empty_latent_caches(
                cfg, len(cfg.mla_layers), capacity)}


def prefill_chunk(params, cfg: LingConfig, ids, caches, pos, n_valid):
    """One chunk of one row: ids (1, T) at positions [pos, pos + T), of
    which the first ``n_valid`` are tokens (the rest padding that leaves
    every cache as it was). Returns (logits after the last valid token
    (1, V), caches, expert stats)."""
    x = params["embed"][ids]
    valid = jnp.arange(ids.shape[1]) < n_valid
    kda, mla = list(caches["kda"]), list(caches["mla"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        if cfg.is_mla(i):
            j = cfg.mla_layers.index(i)
            y, mla[j] = mla_prefill(layer["attn"], cfg, h, mla[j], pos)
        else:
            j = cfg.kda_layers.index(i)
            y, kda[j] = text_layers.kda_prefill(layer["attn"], cfg.delta_rule,
                                                h, kda[j], n_valid)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route,
                                         valid[None])
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)[:, 0]
    return (text_layers.logits_of(params, cfg, last),
            {"kda": kda, "mla": mla}, stats)


def decode_caches(cfg: LingConfig, caches, rows: int, max_new: int):
    """The prompt's caches as ``rows`` rows start from them: recurrent
    state and conv tails broadcast (every row writes its own from the
    first token on), the prompt's latents shared, an empty suffix of
    ``max_new`` latents a row."""
    def spread(x):
        return jnp.broadcast_to(x, (rows,) + x.shape[1:])

    return {"kda": jax.tree.map(spread, caches["kda"]),
            "prompt": caches["mla"],
            "suffix": text_layers.empty_suffixes(cfg, caches["mla"], rows,
                                                 max_new)}


def decode_step(params, cfg: LingConfig, tokens, caches, prompt_len, step):
    """One new token a row: tokens (R,) at position prompt_len + step.
    Returns (logits (R, V), caches, expert stats)."""
    x = params["embed"][tokens][:, None]
    kda, suffix = list(caches["kda"]), list(caches["suffix"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        if cfg.is_mla(i):
            j = cfg.mla_layers.index(i)
            y, suffix[j] = mla_decode(layer["attn"], cfg, h,
                                      caches["prompt"][j], prompt_len,
                                      suffix[j], step)
        else:
            j = cfg.kda_layers.index(i)
            y, kda[j] = text_layers.kda_decode(layer["attn"], cfg.delta_rule,
                                               h, kda[j])
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route)
    caches = {"kda": kda, "prompt": caches["prompt"], "suffix": suffix}
    return text_layers.logits_of(params, cfg, x[:, 0]), caches, stats


def cache_bytes(cfg: LingConfig, rows: int, capacity: int,
                max_new: int) -> dict[str, int]:
    """Bytes of the two kinds of cache a decode of ``rows`` rows holds."""
    item = jnp.dtype(cfg.dtype).itemsize
    recurrent = len(cfg.kda_layers) * rows \
        * text_layers.kda_state_bytes(cfg.delta_rule, item)
    return {"recurrent": recurrent,
            "latent": text_layers.latent_cache_bytes(
                cfg, len(cfg.mla_layers), rows, capacity, max_new)}


def job_counts(cfg: LingConfig, prompt_tokens: int, rows: int, new: int,
               chunk: int, capacity: int) -> dict[str, Any]:
    """What the host knows of one job's two programs, for the counters
    (``pipelines/text.py::TextPipeline._count``): key blocks the decode's
    sweep reads and leaves, query-key pairs a head scores by phase, the
    prefill kernel's grid steps by kind, the latents'
    up-projections by where they are made, the delta-rule prefill's
    sub-blocks by form and the bytes of recurrent state its layers read
    and write by phase, the expert layers."""
    layers = len(cfg.mla_layers)
    return {
        "block_steps": text_layers.prefill_block_steps(
            layers, cfg.num_attention_heads, 1, prompt_tokens, chunk,
            capacity),
        "up_projections": text_layers.prefill_up_projections(
            layers, cfg.num_attention_heads, prompt_tokens, chunk,
            capacity),
        "decode_key_blocks": text_layers.decode_key_blocks(
            layers, prompt_tokens, new, capacity),
        "attention_pairs": text_layers.attention_pairs(
            layers, prompt_tokens, rows, new),
        "kda_blocks": kda_blocks(cfg, prompt_tokens, chunk),
        "recurrent_state_bytes": text_layers.recurrent_state_bytes(
            text_layers.kda_state_bytes(cfg.delta_rule,
                                        jnp.dtype(cfg.dtype).itemsize),
            len(cfg.kda_layers), prompt_tokens, rows, new, chunk),
        "expert_layers": sum(cfg.is_moe(i)
                             for i in range(cfg.num_hidden_layers))}
