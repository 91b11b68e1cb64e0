"""Laguna-XS.2-class decoder: grouped-query softmax attention in every
layer, full and sliding-window layers mixed at different head counts
over the same key-value heads, a per-head output gate, and many small
softmax-routed experts beside one shared expert — the third language
model behind the ``txt2txt`` workflow (pipelines/text.py).

Composed from ``models/text_layers.py``, which it shares with
models/ling.py and models/deepseek.py: the key-value cache core
(``kv_prefill`` / ``kv_decode``), YaRN, the held-experts layer, the
norm, the SwiGLU and the head. This stack's own:

- **the layer pattern**: ``layer_types[i]`` is ``full_attention`` or
  ``sliding_attention`` (a window of ``sliding_window`` keys, the query's
  own among them), ``num_attention_heads_per_layer[i]`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``: query head j
  reads key-value head ``j // (H / Hk)``;
- **rotary settings by layer type** (``rope_parameters``): a full layer
  rotates the first ``partial_rotary_factor`` of each head with YaRN
  frequencies, cos and sin times ``attention_factor`` (the unrotated
  part is not scaled); a sliding layer rotates the whole head with plain
  frequencies; one table for every position; the softmax scale is
  ``head_dim ** -0.5`` in both;
- **the gate**: ``sigmoid(W_g h)``, one value a query head, on that
  head's read-out before W_o;
- **the router**: softmax over ALL experts in float32, the best
  ``num_experts_per_tok`` chosen (ties to the lower index), their
  probabilities normalised to sum 1, times
  ``moe_routed_scaling_factor``; no groups, no bias;
- **the MLPs**: ``mlp_layer_types[i]`` ``dense`` is a SwiGLU of
  ``intermediate_size``, ``sparse`` the experts of
  ``moe_intermediate_size`` beside one shared SwiGLU of
  ``shared_expert_intermediate_size``, added unweighted.

Two kinds of cache in one carry: a full layer's keys and values over the
whole capacity, a sliding layer's last ``sliding_window`` entries
whatever the capacity.
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

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer type's group of the config's ``rope_parameters``
    (``rope_type`` "default" or "yarn")."""

    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    full_attention: Rope = Rope(
        rope_theta=500000.0, partial_rotary_factor=0.5, rope_type="yarn",
        factor=64.0, beta_fast=64.0, beta_slow=1.0,
        attention_factor=1.4158883083359672)
    sliding_attention: Rope = Rope()


_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Sizes by their ``config.json`` names. ``experts_held`` is what
    THIS chip holds: the router keeps ``num_experts`` outputs whatever is
    held."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: tuple[str, ...] = _PERIOD * 10
    num_attention_heads_per_layer: tuple[int, ...] = (48, 64, 64, 64) * 10
    mlp_layer_types: tuple[str, ...] = ("dense",) + ("sparse",) * 39
    sliding_window: int = 512
    rope_parameters: RopeParameters = RopeParameters()
    num_experts: int = 256
    experts_held: tuple[int, int] = (0, 256)    # [first, past the last)
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    #: the module that serves this configuration (models/text_stacks.py)
    stack: ClassVar[str] = "laguna"

    def __post_init__(self):
        n = self.num_hidden_layers
        if not len(self.layer_types) == len(self.mlp_layer_types) \
                == len(self.num_attention_heads_per_layer) == n:
            raise ValueError("the three per-layer lists have one entry a "
                             f"layer ({n})")

    def is_moe(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "sparse"

    def window(self, layer: int) -> int | None:
        """The layer's window; None where it sees every earlier key."""
        return self.sliding_window \
            if self.layer_types[layer] == SLIDING else None

    def layers_of(self, kind: str) -> list[int]:
        return [i for i, t in enumerate(self.layer_types) if t == kind]


#: the CPU tests' size: a period and a half of the layer pattern (2 full
#: layers of 6 heads, 4 sliding ones of 4, over 2 key-value heads of 16),
#: a window of 8 (shorter than a chunk of 16 and than the prompts, and
#: than the tests' new tokens), a dense layer 0 and five expert layers of
#: 16 experts of which 4 are held, and a half-rotated YaRN with every
#: case in its 4 frequency pairs (pairs 0 and 1 kept, pair 2 blended by
#: half, pair 3 divided by the factor)
TINY = LagunaConfig(
    vocab_size=96, hidden_size=64, intermediate_size=96,
    num_hidden_layers=6, num_key_value_heads=2, head_dim=16,
    layer_types=(_PERIOD * 2)[:6],
    num_attention_heads_per_layer=((6, 4, 4, 4) * 2)[:6],
    mlp_layer_types=("dense",) + ("sparse",) * 5, sliding_window=8,
    rope_parameters=RopeParameters(
        full_attention=Rope(
            rope_theta=10000.0, partial_rotary_factor=0.5,
            rope_type="yarn", factor=4.0,
            original_max_position_embeddings=256, beta_fast=4.0,
            beta_slow=0.05, attention_factor=1.1386),
        sliding_attention=Rope(rope_theta=100.0)),
    num_experts=16, experts_held=(0, 4), num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    dtype="float32")


# ---- what a layer type tells the shared key-value core --------------------


def rotary(cfg: LagunaConfig, kind: str) -> tuple[np.ndarray, float]:
    """(the frequencies of the head's rotated values, as many as half of
    them; what cos and sin are scaled by) of a layer type."""
    r = getattr(cfg.rope_parameters, kind)
    dim = int(cfg.head_dim * r.partial_rotary_factor)
    if r.rope_type == "yarn":
        return text_layers.yarn_frequencies(
            dim, r.rope_theta, r.factor, r.original_max_position_embeddings,
            r.beta_fast, r.beta_slow), float(r.attention_factor)
    plain = r.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return plain.astype(np.float32), 1.0


def _told(cfg: LagunaConfig, layer: int) -> dict[str, Any]:
    inv_freq, amplitude = rotary(cfg, cfg.layer_types[layer])
    return {"heads": cfg.num_attention_heads_per_layer[layer],
            "window": cfg.window(layer),
            "inv_freq": jnp.asarray(inv_freq), "rope_amplitude": amplitude,
            "scale": cfg.head_dim ** -0.5}


# ---- checkpoint layout -----------------------------------------------------


def param_shapes(cfg: LagunaConfig) -> dict[str, Any]:
    """The checkpoint's layout as a pytree of ShapeDtypeStruct: what a
    converter (or the benchmark's seeded fill) has to produce."""
    dt = jnp.dtype(cfg.dtype)
    d, dh = cfg.hidden_size, cfg.head_dim
    held = text_layers.n_held(cfg)

    def w(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def swiglu(width, lead=()):
        return {"gate": w(*lead, d, width), "up": w(*lead, d, width),
                "down": w(*lead, width, d)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        h = cfg.num_attention_heads_per_layer[i]
        attn = {"wq": w(d, h * dh),
                "wk": w(d, cfg.num_key_value_heads * dh),
                "wv": w(d, cfg.num_key_value_heads * dh),
                "wg": w(d, h), "wo": w(h * dh, d)}
        if cfg.is_moe(i):
            mlp = {"router": w(d, cfg.num_experts, dtype=jnp.float32),
                   "experts": swiglu(cfg.moe_intermediate_size, (held,)),
                   "shared": swiglu(cfg.shared_expert_intermediate_size)}
        else:
            mlp = swiglu(cfg.intermediate_size)
        layers.append({"attn_norm": w(d), "attn": attn,
                       "mlp_norm": w(d), "mlp": mlp})
    return {"embed": w(cfg.vocab_size, d), "layers": layers,
            "final_norm": w(d), "head": w(d, cfg.vocab_size)}


def random_params(cfg: LagunaConfig, seed: int = 0) -> dict[str, Any]:
    """Host-side random weights for tiny presets (tests, the registry's
    ``allow_random``): projections fan-in scaled, norm gains one."""
    return text_layers.random_fill(param_shapes(cfg), seed)


# ---- the layers ------------------------------------------------------------


def _out(p, x, o):
    """Each head's read-out o (B, T, H, D) times its gate, then W_o."""
    gate = jax.nn.sigmoid(proj(x, p["wg"]).astype(jnp.float32))
    o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    return proj(o.reshape(*x.shape[:2], -1), p["wo"])


def attention_prefill(p, cfg: LagunaConfig, layer: int, x, cache, pos,
                      n_valid):
    """x (1, T, d) at positions [pos, pos + T) -> (y, cache)."""
    o, cache = text_layers.kv_prefill(p, x, cache, pos, n_valid,
                                      **_told(cfg, layer))
    return _out(p, x, o), cache


def attention_decode(p, cfg: LagunaConfig, layer: int, x, prompt,
                     prompt_len, suffix, step):
    """One new token a row: x (R, 1, d) -> (y, suffix)."""
    o, suffix = text_layers.kv_decode(p, x, prompt, prompt_len, suffix,
                                      step, **_told(cfg, layer))
    return _out(p, x, o), suffix


def route(p, cfg: LagunaConfig, x):
    """x (T, d) -> (chosen experts (T, K) int32, their weights (T, K)
    float32): softmax over ALL experts, the best K (ties to the lower
    index), their probabilities normalised to sum 1, times the scaling
    factor."""
    probs = jax.nn.softmax(jnp.dot(x.astype(jnp.float32), p["router"],
                                   precision=HIGHEST), axis=-1)
    weight, chosen = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), weight * cfg.moe_routed_scaling_factor


def moe(p, cfg: LagunaConfig, x, valid=None):
    """x (..., d) -> (shared expert + held experts' part, stats)."""
    return text_layers.moe(p, cfg, x, route, valid)


# ---- the stack -------------------------------------------------------------


def empty_prefill_caches(cfg: LagunaConfig, capacity: int):
    """One row's caches before its first token: keys and values of
    ``capacity`` slots in a full layer, of ``sliding_window`` slots in a
    sliding one."""
    return {"kv": [text_layers.empty_kv_cache(cfg, cfg.window(i) or capacity)
                   for i in range(cfg.num_hidden_layers)]}


def prefill_chunk(params, cfg: LagunaConfig, ids, caches, pos, n_valid):
    """One chunk of one row: ids (1, T) at positions [pos, pos + T), of
    which the first ``n_valid`` are tokens (padding past them writes
    entries of a full layer that no query sees, and none of a sliding
    layer's). Returns (logits after the last valid token (1, V), caches,
    expert stats)."""
    x = params["embed"][ids]
    valid = jnp.arange(ids.shape[1]) < n_valid
    kv = list(caches["kv"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        y, kv[i] = attention_prefill(layer["attn"], cfg, i, h, kv[i], pos,
                                     n_valid)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route,
                                         valid[None])
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)[:, 0]
    return text_layers.logits_of(params, cfg, last), {"kv": kv}, stats


def decode_caches(cfg: LagunaConfig, caches, rows: int, max_new: int):
    """The prompt's keys and values shared by ``rows`` rows (a sliding
    layer's: its last ``sliding_window``), an empty suffix of ``max_new``
    entries a row."""
    return {"prompt": caches["kv"],
            "suffix": [text_layers.empty_kv_cache(cfg, max_new, rows)
                       for _ in caches["kv"]]}


def decode_step(params, cfg: LagunaConfig, tokens, caches, prompt_len,
                step):
    """One new token a row: tokens (R,) at position prompt_len + step.
    Returns (logits (R, V), caches, expert stats)."""
    x = params["embed"][tokens][:, None]
    suffix = list(caches["suffix"])
    stats = empty_stats()
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        y, suffix[i] = attention_decode(layer["attn"], cfg, i, h,
                                        caches["prompt"][i], prompt_len,
                                        suffix[i], step)
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route)
    caches = {"prompt": caches["prompt"], "suffix": suffix}
    return text_layers.logits_of(params, cfg, x[:, 0]), caches, stats


def cache_bytes(cfg: LagunaConfig, rows: int, capacity: int,
                max_new: int) -> dict[str, int]:
    """Bytes of the two kinds of cache a decode of ``rows`` rows holds:
    the full layers' grow with the capacity, the sliding layers' do
    not."""
    return {"full": text_layers.kv_cache_bytes(
                cfg, len(cfg.layers_of(FULL)), rows, capacity, max_new),
            "window": text_layers.kv_cache_bytes(
                cfg, len(cfg.layers_of(SLIDING)), rows, cfg.sliding_window,
                max_new)}


def job_counts(cfg: LagunaConfig, prompt_tokens: int, rows: int, new: int,
               chunk: int, capacity: int) -> dict[str, Any]:
    """What the host knows of one job's two programs, for the counters
    (``pipelines/text.py::TextPipeline._count``): key blocks the kernels
    read and leave, their grid steps by kind, query-key pairs a head
    scores by phase (under the window in a sliding layer), the window's
    pairs visible and scored, the expert layers."""
    full, sliding = cfg.layers_of(FULL), cfg.layers_of(SLIDING)
    window = cfg.sliding_window
    key_blocks, decode_key_blocks = text_layers.kv_key_blocks(
        len(full), prompt_tokens, new, chunk, capacity)
    windowed = text_layers.window_key_blocks(len(sliding), prompt_tokens,
                                             chunk, window)
    of_full = text_layers.attention_pairs(len(full), prompt_tokens, rows, new)
    of_sliding = [text_layers.window_pairs(
        cfg.num_attention_heads_per_layer[i], cfg.num_key_value_heads,
        prompt_tokens, rows, new, chunk, window) for i in sliding]
    # (prefill, decode) inside the window, summed over the sliding layers
    seen = [sum(layer["visible"][phase] for layer in of_sliding)
            for phase in (0, 1)]
    kv_heads = cfg.num_key_value_heads
    block_steps = [text_layers.prefill_block_steps(
        1, kv_heads, cfg.num_attention_heads_per_layer[i] // kv_heads,
        prompt_tokens, chunk, capacity, window if i in sliding else None)
        for i in (*full, *sliding)]
    return {
        "block_steps": {kind: sum(layer[kind] for layer in block_steps)
                        for kind in block_steps[0]},
        "key_blocks": tuple(a + b for a, b in zip(key_blocks, windowed)),
        "decode_key_blocks": decode_key_blocks,
        "attention_pairs": tuple(a + b for a, b in zip(of_full, seen)),
        "window_pairs": {
            "visible": sum(seen),
            "scored": sum(sum(layer["scored"]) for layer in of_sliding)},
        "expert_layers": sum(cfg.is_moe(i)
                             for i in range(cfg.num_hidden_layers))}
