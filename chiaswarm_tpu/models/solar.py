"""Solar-Open2-class decoder: gated delta-rule linear attention in three
layers of every four, gated softmax attention with no position encoding
over grouped keys and values in the fourth, and many sigmoid-routed
experts beside one shared expert in every layer — the fourth language
model behind the ``txt2txt`` workflow (pipelines/text.py).

Composed from ``models/text_layers.py``, which it shares with
models/ling.py (the delta-rule core, the sigmoid router), models/laguna.py
(the key-value cache core) and every text stack (the held-experts layer,
the norm, the SwiGLU and the head). This stack's own:

- **the layer pattern**: layer i is softmax attention where it is one of
  ``gqa_layers`` (0, 4, 8, ...), a delta-rule layer otherwise; every
  layer from ``first_k_dense_replace`` on has experts (all of them here);
- **the delta rule** (``linear_attn_config``: 64 heads of 128, a
  kernel-4 short conv), as ``SolarConfig.delta_rule`` tells the core:
  ``b = 2 sigmoid(W_b x)`` (``kda_allow_neg_eigval``: the transition's
  eigenvalues in (-1, 1]), the softplus decay ``log a = -e^A
  softplus(W_a x + dt_bias)``, and both gates' projections a low-rank
  pair of rank ``head_dim`` (``kda_use_full_proj`` false);
- **the softmax layers**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim`` (query head j
  reads key-value head ``j // (H / Hk)``), no rotary anywhere
  (``use_rope`` false), the softmax scale ``head_dim ** -0.5``, and an
  elementwise sigmoid gate over the H x D read-out before W_o
  (``use_gqa_gate``);
- **the router**: ``text_layers.sigmoid_route`` with one group: sigmoid
  scores over all ``n_routed_experts``, the best
  ``num_experts_per_tok`` by score + bias, their scores normalised to
  sum 1 (``norm_topk_prob``), times ``routed_scaling_factor``; one shared
  SwiGLU of ``moe_intermediate_size`` beside, added unweighted.

Recurrent state beside plain keys and values in one carry: a float32
state and three conv tails per delta-rule layer, a softmax layer's keys
and values over the whole capacity.
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
    param_bytes,
    proj,
    rms_norm,
)
from chiaswarm_tpu.models.text_layers import sigmoid_route as route
from chiaswarm_tpu.obs.trace import scope


@dataclasses.dataclass(frozen=True)
class LinearAttnConfig:
    """The config's ``linear_attn_config`` group (``num_kv_heads`` None:
    as many as ``num_heads``)."""

    short_conv_kernel_size: int = 4
    head_dim: int = 128
    num_heads: int = 64
    num_kv_heads: int | None = None


@dataclasses.dataclass(frozen=True)
class SolarConfig:
    """Sizes by their ``config.json`` names. ``vocab_size`` and
    ``experts_held`` are what THIS chip holds: the router keeps
    ``n_routed_experts`` outputs whatever is held."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_attn_config: LinearAttnConfig = LinearAttnConfig()
    gqa_layers: tuple[int, ...] = tuple(range(0, 48, 4))
    first_k_dense_replace: int = 0
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    experts_held: tuple[int, int] = (0, 320)    # [first, past the last)
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    use_rope: bool = False
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    dtype: str = "bfloat16"
    kda_chunk: int = 64                 # tokens a UT-form chunk holds

    #: the module that serves this configuration (models/text_stacks.py)
    stack: ClassVar[str] = "solar"
    #: ``route``'s groups: none (DeepSeek-V3's names with one group)
    n_group: ClassVar[int] = 1
    topk_group: ClassVar[int] = 1

    def __post_init__(self):
        if self.use_rope or not self.use_gqa_gate \
                or self.kda_use_full_proj or self.n_shared_experts != 1:
            raise ValueError("this stack serves NoPE softmax layers with "
                             "their gate, low-rank delta-rule gates and one "
                             "shared expert")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads come in whole groups")

    def is_gqa(self, layer: int) -> bool:
        return layer in self.gqa_layers

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def kda_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if not self.is_gqa(i)]

    @property
    def softmax_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers) if self.is_gqa(i)]

    @property
    def delta_rule(self) -> text_layers.DeltaRule:
        """What the delta-rule layers tell the core: the softplus decay, a
        write strength up to 2."""
        linear = self.linear_attn_config
        return text_layers.DeltaRule(
            heads=linear.num_heads, head_dim=linear.head_dim,
            conv=linear.short_conv_kernel_size, chunk=self.kda_chunk,
            eps=self.rms_norm_eps, lower_bound=None,
            allow_neg_eigval=self.kda_allow_neg_eigval)


#: the CPU tests' size: a period and a half (G K K K G K), softmax layers
#: of 8 query heads over 2 key-value heads of 16, delta-rule layers of 2
#: heads of 8 (gates of rank 8), a UT chunk of 32 (two row blocks of 16:
#: both forms of the in-chunk matrices), 16 experts of which 4 are held
TINY = SolarConfig(
    vocab_size=96, hidden_size=64, num_hidden_layers=6,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    linear_attn_config=LinearAttnConfig(head_dim=8, num_heads=2),
    gqa_layers=(0, 4), intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=16, experts_held=(0, 4), num_experts_per_tok=4,
    dtype="float32", kda_chunk=32)


# ---- checkpoint layout -----------------------------------------------------


def param_shapes(cfg: SolarConfig) -> dict[str, Any]:
    """The checkpoint's layout as a pytree of ShapeDtypeStruct: what a
    converter (or the benchmark's seeded fill) has to produce."""
    dt = jnp.dtype(cfg.dtype)
    f32 = jnp.float32
    d = cfg.hidden_size
    linear = cfg.linear_attn_config
    h, dk = linear.num_heads, linear.head_dim
    inner = h * dk
    rank = dk                       # both gates' low-rank pairs
    attn_inner = cfg.num_attention_heads * cfg.head_dim
    kv_inner = cfg.num_key_value_heads * cfg.head_dim

    def w(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def swiglu(width, lead=()):
        return {"gate": w(*lead, d, width), "up": w(*lead, d, width),
                "down": w(*lead, width, d)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        if cfg.is_gqa(i):
            attn = {"wq": w(d, attn_inner), "wk": w(d, kv_inner),
                    "wv": w(d, kv_inner), "wgate": w(d, attn_inner),
                    "wo": w(attn_inner, d)}
        else:
            attn = {
                "wq": w(d, inner), "wk": w(d, inner), "wv": w(d, inner),
                "conv_q": w(linear.short_conv_kernel_size, inner),
                "conv_k": w(linear.short_conv_kernel_size, inner),
                "conv_v": w(linear.short_conv_kernel_size, inner),
                "wa_down": w(d, rank), "wa_up": w(rank, inner),
                "a_log": w(h, dtype=f32), "dt_bias": w(inner, dtype=f32),
                "wb": w(d, h), "wg_down": w(d, rank), "wg_up": w(rank, inner),
                "o_norm": w(dk), "wo": w(inner, d)}
        if cfg.is_moe(i):
            mlp = {"router": w(d, cfg.n_routed_experts, dtype=f32),
                   "router_bias": w(cfg.n_routed_experts, dtype=f32),
                   "experts": swiglu(cfg.moe_intermediate_size,
                                     (text_layers.n_held(cfg),)),
                   "shared": swiglu(cfg.moe_intermediate_size)}
        else:
            mlp = swiglu(cfg.intermediate_size)
        layers.append({"attn_norm": w(d), "attn": attn,
                       "mlp_norm": w(d), "mlp": mlp})
    return {"embed": w(cfg.vocab_size, d), "layers": layers,
            "final_norm": w(d), "head": w(d, cfg.vocab_size)}


def random_params(cfg: SolarConfig, seed: int = 0) -> dict[str, Any]:
    """Host-side random weights for tiny presets (tests, the registry's
    ``allow_random``): projections fan-in scaled, norm gains one, the
    decay's bias set so that a channel forgets over a few to tens of
    tokens, the write strength's projection wide enough that b passes 1
    for a good share of the tokens."""
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


# ---- the layers ------------------------------------------------------------


def _out(p, x, o):
    """The read-out o (B, T, H, D) times its elementwise gate, then W_o."""
    gate = jax.nn.sigmoid(proj(x, p["wgate"]).astype(jnp.float32))
    o = (o.reshape(gate.shape).astype(jnp.float32) * gate).astype(x.dtype)
    return proj(o, p["wo"])


def _told(cfg: SolarConfig) -> dict[str, Any]:
    return {"heads": cfg.num_attention_heads, "inv_freq": None,
            "scale": cfg.head_dim ** -0.5}


@scope("attention")
def attention_prefill(p, cfg: SolarConfig, x, cache, pos, n_valid):
    """x (1, T, d) at positions [pos, pos + T) -> (y, cache)."""
    o, cache = text_layers.kv_prefill(p, x, cache, pos, n_valid,
                                      **_told(cfg))
    return _out(p, x, o), cache


@scope("attention")
def attention_decode(p, cfg: SolarConfig, x, prompt, prompt_len, suffix,
                     step):
    """One new token a row: x (R, 1, d) -> (y, suffix)."""
    o, suffix = text_layers.kv_decode(p, x, prompt, prompt_len, suffix,
                                      step, **_told(cfg))
    return _out(p, x, o), suffix


def moe(p, cfg: SolarConfig, x, valid=None):
    """x (..., d) -> (shared expert + held experts' part, stats)."""
    return text_layers.moe(p, cfg, x, route, valid)


# ---- the stack -------------------------------------------------------------


def empty_prefill_caches(cfg: SolarConfig, capacity: int):
    """One row's caches before its first token: a recurrent state and
    conv tails per delta-rule layer, keys and values of ``capacity``
    slots per softmax layer."""
    return {"kda": [text_layers.kda_empty_cache(cfg.delta_rule, 1, cfg.dtype)
                    for _ in cfg.kda_layers],
            "kv": [text_layers.empty_kv_cache(cfg, capacity)
                   for _ in cfg.softmax_layers]}


def prefill_chunk(params, cfg: SolarConfig, ids, caches, pos, n_valid):
    """One chunk of one row: ids (1, T) at positions [pos, pos + T), of
    which the first ``n_valid`` are tokens (padding past them leaves the
    recurrent state as it was and writes keys that no query sees).
    Returns (logits after the last valid token (1, V), caches, expert
    stats)."""
    x = params["embed"][ids]
    valid = jnp.arange(ids.shape[1]) < n_valid
    kda, kv = list(caches["kda"]), list(caches["kv"])
    rule = cfg.delta_rule
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        if cfg.is_gqa(i):
            j = cfg.softmax_layers.index(i)
            y, kv[j] = attention_prefill(layer["attn"], cfg, h, kv[j], pos,
                                         n_valid)
        else:
            j = cfg.kda_layers.index(i)
            y, kda[j] = text_layers.kda_prefill(layer["attn"], rule, h,
                                                kda[j], n_valid)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route,
                                         valid[None])
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)[:, 0]
    return (text_layers.logits_of(params, cfg, last),
            {"kda": kda, "kv": kv}, stats)


def decode_caches(cfg: SolarConfig, caches, rows: int, max_new: int):
    """The prompt's caches as ``rows`` rows start from them: recurrent
    state and conv tails broadcast (every row writes its own from the
    first token on), the prompt's keys and values shared, an empty
    suffix of ``max_new`` entries a row."""
    def spread(x):
        return jnp.broadcast_to(x, (rows,) + x.shape[1:])

    return {"kda": jax.tree.map(spread, caches["kda"]),
            "prompt": caches["kv"],
            "suffix": [text_layers.empty_kv_cache(cfg, max_new, rows)
                       for _ in caches["kv"]]}


def decode_step(params, cfg: SolarConfig, tokens, caches, prompt_len, step):
    """One new token a row: tokens (R,) at position prompt_len + step.
    Returns (logits (R, V), caches, expert stats)."""
    x = params["embed"][tokens][:, None]
    kda, suffix = list(caches["kda"]), list(caches["suffix"])
    rule = cfg.delta_rule
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        if cfg.is_gqa(i):
            j = cfg.softmax_layers.index(i)
            y, suffix[j] = attention_decode(layer["attn"], cfg, h,
                                            caches["prompt"][j], prompt_len,
                                            suffix[j], step)
        else:
            j = cfg.kda_layers.index(i)
            y, kda[j] = text_layers.kda_decode(layer["attn"], rule, h, kda[j])
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route)
    caches = {"kda": kda, "prompt": caches["prompt"], "suffix": suffix}
    return text_layers.logits_of(params, cfg, x[:, 0]), caches, stats


def cache_bytes(cfg: SolarConfig, rows: int, capacity: int,
                max_new: int) -> dict[str, int]:
    """Bytes of the two kinds of cache a decode of ``rows`` rows holds:
    the delta-rule layers' fixed-size state, the softmax layers' keys and
    values over the capacity."""
    item = jnp.dtype(cfg.dtype).itemsize
    return {"recurrent": len(cfg.kda_layers) * rows
            * text_layers.kda_state_bytes(cfg.delta_rule, item),
            "full": text_layers.kv_cache_bytes(
                cfg, len(cfg.softmax_layers), rows, capacity, max_new)}


def job_counts(cfg: SolarConfig, prompt_tokens: int, rows: int, new: int,
               chunk: int, capacity: int) -> dict[str, Any]:
    """What the host knows of one job's two programs, for the counters
    (``pipelines/text.py::TextPipeline._count``): the softmax layers'
    prefill kernel grid steps by kind, key blocks their decode's sweep
    reads and leaves, query-key pairs a head scores by phase; the
    delta-rule prefill's sub-blocks by form and the bytes of recurrent
    state its layers read and write by phase; the expert layers."""
    softmax, kda = cfg.softmax_layers, cfg.kda_layers
    kv_heads = cfg.num_key_value_heads
    return {
        "block_steps": text_layers.prefill_block_steps(
            len(softmax), kv_heads, cfg.num_attention_heads // kv_heads,
            prompt_tokens, chunk, capacity),
        "decode_key_blocks": text_layers.kv_decode_key_blocks(
            len(softmax), prompt_tokens, new, capacity),
        "attention_pairs": text_layers.attention_pairs(
            len(softmax), prompt_tokens, rows, new),
        "kda_blocks": text_layers.kda_blocks(
            cfg.delta_rule, len(kda), prompt_tokens, chunk),
        "recurrent_state_bytes": text_layers.recurrent_state_bytes(
            text_layers.kda_state_bytes(cfg.delta_rule,
                                        jnp.dtype(cfg.dtype).itemsize),
            len(kda), prompt_tokens, rows, new, chunk),
        "expert_layers": sum(cfg.is_moe(i)
                             for i in range(cfg.num_hidden_layers))}
