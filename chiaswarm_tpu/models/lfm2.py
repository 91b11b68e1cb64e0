"""LFM2-MoE-class decoder: gated short convolutions in most layers, softmax
attention with a per-head norm on queries and keys and rotary positions
in the rest, two leading dense layers, then sigmoid-routed experts with
no shared expert, and a head tied to the embedding - the fifth language
model behind the ``txt2txt`` workflow (pipelines/text.py).

Composed from ``models/text_layers.py``, which it shares with the other
text stacks: the gated conv (``gated_conv``: the delta rule's short conv
with no activation), the key-value cache core (models/laguna.py,
models/solar.py), the sigmoid router (models/ling.py, models/solar.py),
the held-experts layer, the norm, the SwiGLU and the head. This stack's
own:

- **the layer pattern**: ``layer_types[i]`` is ``conv`` (the gated short
  convolution, kernel ``conv_L_cache``, no bias) or ``full_attention``;
  the first ``num_dense_layers`` layers have a dense SwiGLU of
  ``intermediate_size``, every later one experts;
- **the attention layers**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` of ``hidden_size / num_attention_heads`` (query
  head j reads key-value head ``j // (H / Hk)``), q and k RMS-normed a
  head (``q_layernorm`` / ``k_layernorm``) and then rotated (rotate-half
  over every value, ``rope_theta``), the softmax scale ``head_dim **
  -0.5``, W_o with no gate; no biases;
- **the router**: ``text_layers.sigmoid_route`` with one group: sigmoid
  scores over all ``num_experts``, the best ``num_experts_per_tok`` by
  score + bias (``use_expert_bias``), their scores over their sum + 1e-6
  (``norm_topk_prob``), times ``routed_scaling_factor``; no shared expert;
- **the head** is the embedding (``tie_word_embeddings``).

Fixed-size state beside plain keys and values in one carry: a conv tail
of ``conv_L_cache - 1`` values a channel per conv layer, an attention
layer's keys and values over the whole capacity.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from chiaswarm_tpu.models import text_layers
from chiaswarm_tpu.models.text_layers import (  # noqa: F401
    empty_stats,
    param_bytes,
    proj,
    rms_norm,
)
from chiaswarm_tpu.models.text_layers import sigmoid_route as route
from chiaswarm_tpu.obs.trace import scope

CONV, ATTENTION = "conv", "full_attention"

#: the published ``layer_types``: 18 conv layers and 6 attention layers
LAYER_TYPES = tuple(ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV
                    for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """Sizes by their ``config.json`` names. ``experts_held`` is what THIS
    chip holds: the router keeps ``num_experts`` outputs whatever is
    held."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple[str, ...] = LAYER_TYPES
    num_dense_layers: int = 2
    num_experts: int = 32
    experts_held: tuple[int, int] = (0, 32)    # [first, past the last)
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"

    #: the module that serves this configuration (models/text_stacks.py)
    stack: ClassVar[str] = "lfm2"
    #: ``route``'s groups: none
    n_group: ClassVar[int] = 1
    topk_group: ClassVar[int] = 1
    #: ``norm_topk_prob``'s guard: weights s / (sum of the chosen s + 1e-6)
    norm_topk_eps: ClassVar[float] = 1e-6

    def __post_init__(self):
        if self.conv_bias or not self.tie_word_embeddings \
                or not self.use_expert_bias or not self.norm_topk_prob:
            raise ValueError("this stack serves bias-free convs, a tied "
                             "head and a biased, normalised router")
        if len(self.layer_types) != self.num_hidden_layers \
                or not set(self.layer_types) <= {CONV, ATTENTION}:
            raise ValueError("one layer type a layer: conv or "
                             "full_attention")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads split the width, and query heads come "
                             "in whole groups")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rms_norm_eps(self) -> float:
        """``norm_eps`` by the name the shared layers read."""
        return self.norm_eps

    def is_attention(self, layer: int) -> bool:
        return self.layer_types[layer] == ATTENTION

    def is_moe(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    @property
    def conv_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if not self.is_attention(i)]

    @property
    def attention_layers(self) -> list[int]:
        return [i for i in range(self.num_hidden_layers)
                if self.is_attention(i)]


#: the CPU tests' size: the two dense conv layers and one period (A C C
#: C), attention of 8 query heads over 2 key-value heads of 16, 16
#: experts of which all are held
TINY = Lfm2Config(
    vocab_size=96, hidden_size=128, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=6, num_attention_heads=8,
    num_key_value_heads=2, layer_types=LAYER_TYPES[:6], num_experts=16,
    experts_held=(0, 16), dtype="float32")


# ---- checkpoint layout -----------------------------------------------------


def param_shapes(cfg: Lfm2Config) -> dict[str, Any]:
    """The checkpoint's layout as a pytree of ShapeDtypeStruct: what a
    converter (or the benchmark's seeded fill) has to produce. No head:
    it is the embedding."""
    dt = jnp.dtype(cfg.dtype)
    d, dh = cfg.hidden_size, cfg.head_dim
    kv_inner = cfg.num_key_value_heads * dh

    def w(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def swiglu(width, lead=()):
        return {"gate": w(*lead, d, width), "up": w(*lead, d, width),
                "down": w(*lead, width, d)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        if cfg.is_attention(i):
            mixer = {"wq": w(d, d), "wk": w(d, kv_inner),
                     "wv": w(d, kv_inner), "wo": w(d, d),
                     "q_norm": w(dh), "k_norm": w(dh)}
        else:
            mixer = {"in_proj": w(d, 3 * d), "conv": w(cfg.conv_L_cache, d),
                     "out_proj": w(d, d)}
        if cfg.is_moe(i):
            mlp = {"router": w(d, cfg.num_experts, dtype=jnp.float32),
                   "router_bias": w(cfg.num_experts, dtype=jnp.float32),
                   "experts": swiglu(cfg.moe_intermediate_size,
                                     (text_layers.n_held(cfg),))}
        else:
            mlp = swiglu(cfg.intermediate_size)
        layers.append({"attn_norm": w(d), "attn": mixer,
                       "mlp_norm": w(d), "mlp": mlp})
    return {"embed": w(cfg.vocab_size, d), "layers": layers,
            "final_norm": w(d)}


def random_params(cfg: Lfm2Config, seed: int = 0) -> dict[str, Any]:
    """Host-side random weights for tiny presets (tests, the registry's
    ``allow_random``): projections fan-in scaled, norm gains one, conv
    taps and the router's bias wide enough to matter, the embedding (the
    head too) at the head's fan-in scale."""
    import numpy as np

    rng = np.random.RandomState(seed)

    def fill(path, spec):
        name = path[-1].key
        if name.endswith("norm"):
            value = np.ones(spec.shape, np.float32)
        elif name == "conv":
            value = rng.normal(0.0, 0.5, spec.shape)
        elif name == "router_bias":
            value = rng.normal(0.0, 0.1, spec.shape)
        elif name == "embed":
            value = rng.normal(0.0, spec.shape[-1] ** -0.5, spec.shape)
        else:  # (..., fan_in, fan_out)
            value = rng.normal(0.0, spec.shape[-2] ** -0.5, spec.shape)
        return jnp.asarray(value, spec.dtype)

    return jax.tree_util.tree_map_with_path(fill, param_shapes(cfg))


# ---- the layers ------------------------------------------------------------


def _told(cfg: Lfm2Config) -> dict[str, Any]:
    return {"heads": cfg.num_attention_heads,
            "inv_freq": text_layers.rope_frequencies(cfg.rope_theta,
                                                     cfg.head_dim),
            "scale": cfg.head_dim ** -0.5, "qk_norm_eps": cfg.norm_eps}


def _out(p, x, o):
    return proj(o.reshape(*x.shape[:2], -1), p["wo"])


@scope("attention")
def attention_prefill(p, cfg: Lfm2Config, x, cache, pos, n_valid):
    """x (1, T, d) at positions [pos, pos + T) -> (y, cache)."""
    o, cache = text_layers.kv_prefill(p, x, cache, pos, n_valid,
                                      **_told(cfg))
    return _out(p, x, o), cache


@scope("attention")
def attention_decode(p, cfg: Lfm2Config, x, prompt, prompt_len, suffix,
                     step):
    """One new token a row: x (R, 1, d) -> (y, suffix)."""
    o, suffix = text_layers.kv_decode(p, x, prompt, prompt_len, suffix,
                                      step, **_told(cfg))
    return _out(p, x, o), suffix


def moe(p, cfg: Lfm2Config, x, valid=None):
    """x (..., d) -> (held experts' part, stats)."""
    return text_layers.moe(p, cfg, x, route, valid)


# ---- the stack -------------------------------------------------------------


def empty_prefill_caches(cfg: Lfm2Config, capacity: int):
    """One row's caches before its first token: a zero conv tail per conv
    layer, keys and values of ``capacity`` slots per attention layer."""
    tail = (1, cfg.conv_L_cache - 1, cfg.hidden_size)
    return {"conv": [jnp.zeros(tail, jnp.dtype(cfg.dtype))
                     for _ in cfg.conv_layers],
            "kv": [text_layers.empty_kv_cache(cfg, capacity)
                   for _ in cfg.attention_layers]}


def prefill_chunk(params, cfg: Lfm2Config, ids, caches, pos, n_valid):
    """One chunk of one row: ids (1, T) at positions [pos, pos + T), of
    which the first ``n_valid`` are tokens (padding past them leaves the
    conv tails as they were and writes keys that no query sees). Returns
    (logits after the last valid token (1, V), caches, expert stats)."""
    x = params["embed"][ids]
    valid = jnp.arange(ids.shape[1]) < n_valid
    conv, kv = list(caches["conv"]), list(caches["kv"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        if cfg.is_attention(i):
            j = cfg.attention_layers.index(i)
            y, kv[j] = attention_prefill(layer["attn"], cfg, h, kv[j], pos,
                                         n_valid)
        else:
            j = cfg.conv_layers.index(i)
            y, conv[j] = text_layers.gated_conv(layer["attn"], h, conv[j],
                                                n_valid)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route,
                                         valid[None])
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)[:, 0]
    return (text_layers.logits_of(params, cfg, last),
            {"conv": conv, "kv": kv}, stats)


def decode_caches(cfg: Lfm2Config, caches, rows: int, max_new: int):
    """The prompt's caches as ``rows`` rows start from them: conv tails
    broadcast (every row rolls its own from the first token on), the
    prompt's keys and values shared, an empty suffix of ``max_new``
    entries a row."""
    return {"conv": [jnp.broadcast_to(t, (rows,) + t.shape[1:])
                     for t in caches["conv"]],
            "prompt": caches["kv"],
            "suffix": [text_layers.empty_kv_cache(cfg, max_new, rows)
                       for _ in caches["kv"]]}


def decode_step(params, cfg: Lfm2Config, tokens, caches, prompt_len, step):
    """One new token a row: tokens (R,) at position prompt_len + step.
    Returns (logits (R, V), caches, expert stats)."""
    x = params["embed"][tokens][:, None]
    conv, suffix = list(caches["conv"]), list(caches["suffix"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        if cfg.is_attention(i):
            j = cfg.attention_layers.index(i)
            y, suffix[j] = attention_decode(layer["attn"], cfg, h,
                                            caches["prompt"][j], prompt_len,
                                            suffix[j], step)
        else:
            j = cfg.conv_layers.index(i)
            y, conv[j] = text_layers.gated_conv(layer["attn"], h, conv[j], 1)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route)
    caches = {"conv": conv, "prompt": caches["prompt"], "suffix": suffix}
    return text_layers.logits_of(params, cfg, x[:, 0]), caches, stats


def conv_tail_bytes(cfg: Lfm2Config) -> int:
    """Bytes of one row's conv tail in one conv layer."""
    return (cfg.conv_L_cache - 1) * cfg.hidden_size \
        * jnp.dtype(cfg.dtype).itemsize


def cache_bytes(cfg: Lfm2Config, rows: int, capacity: int,
                max_new: int) -> dict[str, int]:
    """Bytes of the two kinds of cache a decode of ``rows`` rows holds:
    the conv layers' tails, the attention layers' keys and values over
    the capacity."""
    return {"recurrent": len(cfg.conv_layers) * rows * conv_tail_bytes(cfg),
            "full": text_layers.kv_cache_bytes(
                cfg, len(cfg.attention_layers), rows, capacity, max_new)}


def job_counts(cfg: Lfm2Config, prompt_tokens: int, rows: int, new: int,
               chunk: int, capacity: int) -> dict[str, Any]:
    """What the host knows of one job's two programs, for the counters
    (``pipelines/text.py::TextPipeline._count``): the attention layers'
    prefill kernel grid steps by kind, key blocks their decode's sweep
    reads and leaves, query-key pairs a head scores by phase; the bytes
    of conv tail the conv layers read and write by phase; the expert
    layers."""
    attention = cfg.attention_layers
    kv_heads = cfg.num_key_value_heads
    return {
        "block_steps": text_layers.prefill_block_steps(
            len(attention), kv_heads, cfg.num_attention_heads // kv_heads,
            prompt_tokens, chunk, capacity),
        "decode_key_blocks": text_layers.kv_decode_key_blocks(
            len(attention), prompt_tokens, new, capacity),
        "attention_pairs": text_layers.attention_pairs(
            len(attention), prompt_tokens, rows, new),
        "recurrent_state_bytes": text_layers.recurrent_state_bytes(
            conv_tail_bytes(cfg), len(cfg.conv_layers), prompt_tokens, rows,
            new, chunk),
        "expert_layers": sum(cfg.is_moe(i)
                             for i in range(cfg.num_hidden_layers))}
