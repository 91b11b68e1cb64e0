"""Layers the text stacks share (models/ling.py, models/deepseek.py,
models/laguna.py): the norm, the SwiGLU and the rotary pieces (YaRN
among them), latent attention (MLA) with its compressed cache, attention
over plain keys and values with its two kinds of cache, and the expert
layer of which a chip holds a part. One implementation of each; a stack
tells them what differs.

- **Latent attention.** The cache holds 512 normed latent + 64 rotated
  key values a token (``kv_lora_rank + qk_rope_head_dim``), one entry for
  all heads. Two compute paths: prefill up-projects keys and values and
  goes through ``ops.attention`` (causal), decode folds the key
  up-projection into the query and the value up-projection after the
  softmax (the absorbed form), against the latents directly: a prompt's
  latents shared by the rows (one key-blocked sweep on the chip,
  ``ops.attention.shared_latent_attention``: the scores never reach
  memory), each row's own suffix beside them, joined exactly. A stack
  hands in its query projection (full rank, or through a bottleneck with
  its own norm), its rotary frequencies (plain, or YaRN's), the softmax
  scale, and does what follows the heads' read-out itself (a gate or
  none, then W_o).
- **Keys and values.** Grouped-query attention over a cache of each
  token's keys and values (``kv_heads x head_dim`` of each). A stack
  hands in the layer's head count, its rotary frequencies (as many as
  half the values of a head that are rotated: all of them, or the first
  part), their amplitude, the softmax scale and, for a sliding layer,
  the window. A FULL layer's cache has the capacity's slots; its prefill
  writes a chunk and attends causally over what is written, its decode
  sweeps the prompt's keys and values once a key-value head for all
  rows (``ops.attention.shared_prompt_attention``) and joins each row's
  own suffix exactly, as the latent core does. A SLIDING layer's cache
  holds the last ``window`` entries whatever the capacity: its prefill
  lays them and the chunk's into one local buffer of ``window + chunk``
  slots and attends under the window (``ops.attention(window=...)``),
  its decode scores those ``window`` entries and the row's suffix in one
  masked softmax (a few hundred keys: no kernel), and is right when the
  suffix outgrows the window.
- **Experts.** The layer is told which experts it holds
  (``experts_held``), routes over all of them by the stack's router
  (sigmoid with a bias and normalised weights, or softmax over groups'
  maxima with plain weights), and computes its own experts' part for the
  tokens routed to them (plus the shared expert); what the absent
  experts would add is left out. Tokens are grouped by expert into
  blocks and a loop with a dynamic trip count walks the blocks in use,
  so a step reads the weights of the experts that were hit and no
  others, and no token is ever dropped.

A configuration is any object with the published key names these
functions read (``num_attention_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rms_norm_eps``, ``num_experts_per_tok``, ``experts_held``). Weights
and activations in the configuration's dtype (bfloat16 served), the
router in float32 with ``Precision.HIGHEST`` (a float32 product is one
bfloat16 pass on a TPU otherwise).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chiaswarm_tpu.ops.attention import (
    attention,
    shared_latent_attention,
    shared_prompt_attention,
)
from chiaswarm_tpu.ops.causal_flash_attention import (
    block_steps,
    key_block,
    prompt_key_block,
    shared_key_block,
    stepped_pairs,
    window_key_block,
)

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


# ---- pieces every layer uses ---------------------------------------------


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def proj(x, w):
    """x @ w in the activations' dtype, accumulated in float32."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(p, x):
    return proj(jax.nn.silu(proj(x, p["gate"])) * proj(x, p["up"]),
                p["down"])


def rope_frequencies(theta: float, dim: int):
    """The ``dim // 2`` plain rotary frequencies ``theta^(-2i/dim)``."""
    half = dim // 2
    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def rope(x, positions, inv_freq, amplitude: float = 1.0):
    """Rotate-half RoPE over the last axis of ``x`` (..., T, D) at
    integer ``positions`` (T,) with the D/2 frequencies ``inv_freq``, in
    float32; cos and sin times ``amplitude`` (YaRN's ratio of mscales)."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_band(dim: int, theta: float, original: int, beta_fast: float,
              beta_slow: float) -> tuple[int, int]:
    """(low, high) of YaRN over ``dim`` rotated values: frequency pairs
    below ``low`` keep their frequency (more than ``beta_fast`` turns
    over the ``original`` positions), pairs from ``high`` on are
    interpolated (fewer than ``beta_slow``), those between blended."""
    def pair_with(turns: float) -> float:
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(pair_with(beta_fast)), 0),
            min(math.ceil(pair_with(beta_slow)), dim - 1))


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's ``dim // 2`` rotary frequencies, float32: the plain ones
    ``theta^(-2i/dim)`` below the band, divided by ``factor`` past it, a
    linear ramp in i between."""
    low, high = yarn_band(dim, theta, original, beta_fast, beta_slow)
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    keep = 1.0 - ramp
    return (plain / factor * (1.0 - keep) + plain * keep).astype(np.float32)


def logits_of(params, cfg, x):
    """Hidden states (..., d) -> float32 logits over the slice held."""
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def param_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))


def random_fill(shapes, seed: int):
    """Host-side random weights in a checkpoint layout (a pytree of
    ShapeDtypeStruct), for tiny presets: kernels (..., fan_in, fan_out)
    fan-in scaled, the embedding unit normal, norm gains one."""
    rng = np.random.RandomState(seed)

    def fill(path, spec):
        name = path[-1].key
        if name.endswith("norm"):
            value = np.ones(spec.shape, np.float32)
        elif name == "embed":
            value = rng.normal(0.0, 1.0, spec.shape)
        else:
            value = rng.normal(0.0, spec.shape[-2] ** -0.5, spec.shape)
        return jnp.asarray(value, spec.dtype)

    return jax.tree_util.tree_map_with_path(fill, shapes)


# ---- latent attention ----------------------------------------------------


def latent_width(cfg) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def _query_and_entry(p, cfg, x, q, positions, inv_freq, amplitude):
    """From the stack's query projection ``q`` (B, T, H * (Dn + Dr)):
    q_nope (B, T, H, Dn), rotated q_rope (B, T, H, Dr), and the cache
    entry (B, T, latent + Dr): the normed latent and the rotated shared
    key of the normed input ``x``."""
    b_, t, _ = x.shape
    q = q.reshape(b_, t, cfg.num_attention_heads, -1)
    q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    q_r = rope(jnp.swapaxes(q_r, 1, 2), positions, inv_freq, amplitude)
    q_r = jnp.swapaxes(q_r, 1, 2).astype(x.dtype)
    ckr = proj(x, p["wdkv"])
    c = rms_norm(ckr[..., :cfg.kv_lora_rank], p["kv_norm"],
                 cfg.rms_norm_eps)
    k_r = rope(ckr[..., cfg.kv_lora_rank:], positions, inv_freq,
               amplitude).astype(x.dtype)
    return q_n, q_r, jnp.concatenate([c, k_r], axis=-1)


def _up_projections(p, cfg):
    """W_uk (rank, H, Dn) and W_uv (rank, H, Dv) of the stacked W_ukv."""
    wukv = p["wukv"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads, -1)
    return (wukv[..., :cfg.qk_nope_head_dim],
            wukv[..., cfg.qk_nope_head_dim:])


def latent_prefill(p, cfg, x, q, cache, pos, *, inv_freq, scale: float,
                   rope_amplitude: float = 1.0):
    """The heads' read-out (B, T, H, Dv) of x (B, T, d) at positions
    [pos, pos + T), and the cache with their entries written; ``cache``
    (B, S, latent + Dr) holds every earlier token's. Up-projects the
    latents to keys and values block by block, as far as the cache is
    written and no further, and attends causally (``ops.attention``)
    over the same blocks; the rotary key goes in as it lies in the
    cache, one for all heads."""
    b_, t, _ = x.shape
    h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    q_n, q_r, entry = _query_and_entry(p, cfg, x, q, pos + jnp.arange(t),
                                       inv_freq, rope_amplitude)
    cache = jax.lax.dynamic_update_slice_in_dim(cache, entry, pos, axis=1)
    s = cache.shape[1]
    block = key_block(t, s)
    w_uk, w_uv = (w.reshape(rank, -1) for w in _up_projections(p, cfg))

    def up_project(i, kv):
        latents = jax.lax.dynamic_slice_in_dim(
            cache, i * block, block, axis=1)[..., :rank]
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                whole, proj(latents, w), i * block, axis=1)
            for whole, w in zip(kv, (w_uk, w_uv)))

    # blocks past the written length stay zero and are never read
    k_n, v = jax.lax.fori_loop(
        0, (pos + t + block - 1) // block, up_project,
        tuple(jnp.zeros((b_, s, w.shape[1]), x.dtype)
              for w in (w_uk, w_uv)))
    o = attention(q_n, k_n.reshape(b_, s, h, -1), v.reshape(b_, s, h, -1),
                  scale=scale, causal=True, q_offset=pos,
                  shared_key=(q_r, cache[..., rank:]))
    return o, cache


def latent_decode(p, cfg, x, q, prompt_cache, prompt_len, suffix, step, *,
                  inv_freq, scale: float, rope_amplitude: float = 1.0):
    """The absorbed form for one new token a row: the heads' read-out
    (R, 1, H, Dv) of x (R, 1, d) and the suffix with the rows' entries
    written. ``prompt_cache`` (1, S, W) is shared by the rows (the first
    ``prompt_len`` entries are valid, at least one), ``suffix`` (R, N, W)
    is each row's own (entries [0, step] valid after this call's write).

    The rows' heads are R x H queries against ONE key/value head, so the
    prompt's part (scores, mask, softmax, read-out) is one key-blocked
    sweep on the chip (``ops.attention.shared_latent_attention``): no
    (R, H, S) array exists in memory, and key blocks past ``prompt_len``
    are not read. The suffix's part, at most N keys a row, stays here;
    the two partial softmaxes are joined by their log-sum-exps in
    float32, which is the softmax over prompt + suffix."""
    rank = cfg.kv_lora_rank
    position = (prompt_len + step)[None]
    q_n, q_r, entry = _query_and_entry(p, cfg, x, q, position, inv_freq,
                                       rope_amplitude)
    suffix = jax.lax.dynamic_update_slice_in_dim(suffix, entry, step, axis=1)
    w_uk, w_uv = _up_projections(p, cfg)
    q_abs = jnp.einsum("rhd,chd->rhc", q_n[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    q_all = jnp.concatenate([q_abs, q_r[:, 0]], axis=-1)      # (R, H, W)
    rows, h, width = q_all.shape
    o_prompt, lse_prompt = shared_latent_attention(
        q_all.reshape(rows * h, width), prompt_cache[0], prompt_len,
        value_width=rank, scale=scale)
    o_prompt = o_prompt.reshape(rows, h, rank)
    lse_prompt = lse_prompt.reshape(rows, h)
    s_own = jnp.einsum("rhw,rnw->rhn", q_all, suffix,
                       preferred_element_type=jnp.float32)
    s_own = jnp.where(jnp.arange(suffix.shape[1]) <= step, s_own,
                      NEG_INF) * scale
    lse = jnp.logaddexp(lse_prompt, jax.nn.logsumexp(s_own, axis=-1))
    w_own = jnp.exp(s_own - lse[..., None]).astype(x.dtype)
    o_lat = o_prompt * jnp.exp(lse_prompt - lse)[..., None] \
        + jnp.einsum("rhn,rnc->rhc", w_own, suffix[..., :rank],
                     preferred_element_type=jnp.float32)
    o = jnp.einsum("rhc,chd->rhd", o_lat.astype(x.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    return o[:, None].astype(x.dtype), suffix


def empty_latent_caches(cfg, layers: int, capacity: int):
    """One row's latent cache of ``capacity`` entries per layer."""
    return [jnp.zeros((1, capacity, latent_width(cfg)),
                      jnp.dtype(cfg.dtype)) for _ in range(layers)]


def empty_suffixes(cfg, prompt_caches, rows: int, max_new: int):
    """An empty suffix of ``max_new`` latents a row per layer."""
    return [jnp.zeros((rows, max_new, latent_width(cfg)), c.dtype)
            for c in prompt_caches]


def latent_cache_bytes(cfg, layers: int, rows: int, capacity: int,
                       max_new: int) -> int:
    return layers * (capacity + rows * max_new) * latent_width(cfg) \
        * jnp.dtype(cfg.dtype).itemsize


# what the host knows of a job, for the counters (no callback in a jit)


def prefill_key_blocks(layers: int, prompt_tokens: int, chunk: int,
                       capacity: int) -> tuple[int, int]:
    """(key blocks ``latent_prefill`` reads over a prompt's chunks, key
    blocks of the whole capacity over the same chunks), summed over
    ``layers`` latent-attention layers."""
    block = key_block(chunk, capacity)
    starts = range(0, prompt_tokens, chunk)
    return (layers * sum(-(-(pos + chunk) // block) for pos in starts),
            layers * len(starts) * -(-capacity // block))


def prefill_block_steps(layers: int, grid_heads: int, g: int,
                        prompt_tokens: int, chunk: int, capacity: int,
                        window: int | None = None) -> dict[str, int]:
    """Grid steps of ``layers`` like attention layers' prefill kernel
    over a prompt's chunks by kind (``whole`` / ``diagonal`` / ``dead``),
    summed over a layer's ``grid_heads`` heads of the grid (the query
    heads of a latent layer; the key-value heads of a grouped one, ``g``
    query heads' rows each). Sliding layers (``window``) sweep their
    local buffer of ``window + chunk`` slots."""
    total = {"whole": 0, "diagonal": 0, "dead": 0}
    for pos in range(0, prompt_tokens, chunk):
        if window is None:
            steps = block_steps(chunk, g, pos, capacity)
        else:
            steps = block_steps(chunk, g, min(pos, window), window + chunk,
                                window)
        for kind in total:
            total[kind] += layers * grid_heads * steps[kind]
    return total


def decode_key_blocks(layers: int, prompt_tokens: int, new: int,
                      capacity: int) -> tuple[int, int]:
    """(key blocks ``latent_decode``'s sweep reads over a job's ``new -
    1`` decode steps, key blocks of the whole capacity over the same
    steps), summed over ``layers`` latent-attention layers."""
    block = shared_key_block(capacity)
    steps = layers * (new - 1)
    return steps * -(-prompt_tokens // block), steps * -(-capacity // block)


def attention_pairs(layers: int, prompt_tokens: int, rows: int,
                    new: int) -> tuple[int, int]:
    """(query-key pairs a head scores in a prompt's prefill, pairs it
    scores in the decode of ``rows`` rows), summed over ``layers``
    latent-attention layers: a prompt token sees the tokens up to
    itself, a decode step (``new - 1`` of them) the prompt and the row's
    suffix up to its own entry."""
    prefill = prompt_tokens * (prompt_tokens + 1) // 2
    steps = new - 1
    decode = rows * (steps * (prompt_tokens + 1) + steps * (steps - 1) // 2)
    return layers * prefill, layers * decode


# ---- keys and values: grouped-query attention, full or windowed -------------


def _rotated(x, positions, inv_freq, amplitude: float):
    """x (B, T, H, D) with the first ``2 x len(inv_freq)`` values of
    every head rotated (all of them, or a leading part: the rest passes
    as it is, unscaled)."""
    width = 2 * inv_freq.shape[0]
    part = rope(jnp.swapaxes(x[..., :width], 1, 2), positions, inv_freq,
                amplitude)
    part = jnp.swapaxes(part, 1, 2).astype(x.dtype)
    if width == x.shape[-1]:
        return part
    return jnp.concatenate([part, x[..., width:]], axis=-1)


def _queries_keys_values(p, x, positions, heads: int, kv_heads: int,
                         inv_freq, amplitude: float):
    """q (B, T, H, D) and k, v (B, T, Hk, D) of the normed input x
    (B, T, d), q and k rotated at ``positions`` (T,)."""
    b_, t, _ = x.shape
    q = proj(x, p["wq"]).reshape(b_, t, heads, -1)
    k = proj(x, p["wk"]).reshape(b_, t, kv_heads, -1)
    v = proj(x, p["wv"]).reshape(b_, t, kv_heads, -1)
    return (_rotated(q, positions, inv_freq, amplitude),
            _rotated(k, positions, inv_freq, amplitude), v)


def _written(cache, k, v, at):
    return {"k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, at,
                                                     axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, at,
                                                     axis=1)}


def kv_prefill(p, x, cache, pos, n_valid, *, heads: int, inv_freq,
               scale: float, rope_amplitude: float = 1.0,
               window: int | None = None):
    """The heads' read-out (1, T, H, D) of the normed x (1, T, d) at
    positions [pos, pos + T), of which the first ``n_valid`` are tokens,
    and the cache with their entries written. ``cache`` = {"k", "v"}
    (1, S, Hk, D).

    A full layer (``window`` None): S is the capacity, slot c holds
    position c, the chunk is written at ``pos`` and attends causally
    over what is written. A sliding layer: S = ``window``, the cache
    holds the ``window`` entries before ``pos`` (slot c holds position
    ``max(pos - window, 0) + c``; slots past ``pos`` hold nothing while
    the sequence is shorter than the window). They and the chunk's are
    laid into one local buffer of ``window + T`` slots, over which a
    query sees the ``window`` keys up to its own; the ``window`` entries
    before ``pos + n_valid`` are what the next call (or the decode)
    gets."""
    kv_heads = cache["k"].shape[2]
    t = x.shape[1]
    q, k, v = _queries_keys_values(p, x, pos + jnp.arange(t), heads,
                                   kv_heads, inv_freq, rope_amplitude)
    if window is None:
        cache = _written(cache, k, v, pos)
        o = attention(q, cache["k"], cache["v"], scale=scale, causal=True,
                      q_offset=pos)
        return o, cache
    first = jnp.maximum(pos - window, 0)        # position of slot 0
    local = _written(
        {name: jnp.concatenate([cache[name], jnp.zeros_like(new)], axis=1)
         for name, new in (("k", k), ("v", v))}, k, v, pos - first)
    o = attention(q, local["k"], local["v"], scale=scale, causal=True,
                  q_offset=pos - first, window=window)
    keep = jnp.maximum(pos + n_valid - window, 0) - first
    return o, {name: jax.lax.dynamic_slice_in_dim(whole, keep, window,
                                                  axis=1)
               for name, whole in local.items()}


def kv_decode(p, x, prompt, prompt_len, suffix, step, *, heads: int,
              inv_freq, scale: float, rope_amplitude: float = 1.0,
              window: int | None = None):
    """One new token a row: the heads' read-out (R, 1, H, D) of the
    normed x (R, 1, d) at position ``prompt_len + step`` and the suffix
    with the rows' entries written. ``prompt`` = {"k", "v"} (1, S, Hk, D)
    is what ``kv_prefill`` left, shared by the rows; ``suffix`` = {"k",
    "v"} (R, N, Hk, D) is each row's own (slot n holds position
    ``prompt_len + n``; [0, step] valid after this call's write).

    A full layer: the prompt's part is one key-blocked sweep on the chip
    (``ops.attention.shared_prompt_attention``: a key-value head's keys
    and values read once for all rows and its G query heads, no
    (R, H, S) array in memory, blocks past ``prompt_len`` not read); the
    suffix's part stays here, and the two partial softmaxes are joined
    by their log-sum-exps in float32. A sliding layer: the prompt's last
    ``window`` entries and the suffix in one masked softmax over
    ``window + N`` keys; a key is seen while it lies less than ``window``
    behind the query, so the prompt's entries leave one by one and, once
    ``step >= window``, the suffix's oldest too."""
    kv_heads = prompt["k"].shape[2]
    q, k, v = _queries_keys_values(p, x, (prompt_len + step)[None], heads,
                                   kv_heads, inv_freq, rope_amplitude)
    suffix = _written(suffix, k, v, step)
    rows, n = suffix["k"].shape[:2]
    q = q[:, 0].reshape(rows, kv_heads, heads // kv_heads, -1)
    slot = jnp.arange(n)
    own = slot <= step
    if window is not None:
        own &= slot > step - window
    s_own = jnp.einsum("rkgd,rnkd->rkgn", q, suffix["k"],
                       preferred_element_type=jnp.float32)
    s_own = jnp.where(own, s_own, NEG_INF) * scale
    if window is None:
        o_prompt, lse_prompt = shared_prompt_attention(
            q.reshape(rows, heads, -1), prompt["k"][0], prompt["v"][0],
            prompt_len, scale=scale)
        o_prompt = o_prompt.reshape(q.shape)
        lse_prompt = lse_prompt.reshape(q.shape[:3])
        lse = jnp.logaddexp(lse_prompt, jax.nn.logsumexp(s_own, axis=-1))
        w_own = jnp.exp(s_own - lse[..., None]).astype(x.dtype)
        o = o_prompt * jnp.exp(lse_prompt - lse)[..., None] \
            + jnp.einsum("rkgn,rnkd->rkgd", w_own, suffix["v"],
                         preferred_element_type=jnp.float32)
    else:
        position = jnp.maximum(prompt_len - window, 0) + jnp.arange(window)
        seen = (position < prompt_len) \
            & (position > prompt_len + step - window)
        s_prompt = jnp.einsum("rkgd,ckd->rkgc", q, prompt["k"][0],
                              preferred_element_type=jnp.float32)
        s_prompt = jnp.where(seen, s_prompt, NEG_INF) * scale
        w = jax.nn.softmax(jnp.concatenate([s_prompt, s_own], axis=-1),
                           axis=-1).astype(x.dtype)
        o = jnp.einsum("rkgc,ckd->rkgd", w[..., :window], prompt["v"][0],
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("rkgn,rnkd->rkgd", w[..., window:], suffix["v"],
                         preferred_element_type=jnp.float32)
    return o.reshape(rows, 1, heads, -1).astype(x.dtype), suffix


def empty_kv_cache(cfg, slots: int, rows: int = 1):
    """{"k", "v"} of ``slots`` empty entries a row."""
    shape = (rows, slots, cfg.num_key_value_heads, cfg.head_dim)
    return {name: jnp.zeros(shape, jnp.dtype(cfg.dtype))
            for name in ("k", "v")}


def kv_cache_bytes(cfg, layers: int, rows: int, slots: int,
                   max_new: int) -> int:
    """Bytes of ``layers`` layers' keys and values in a decode of
    ``rows`` rows: the prompt's ``slots`` entries once, ``max_new`` a
    row."""
    return layers * (slots + rows * max_new) * 2 \
        * cfg.num_key_value_heads * cfg.head_dim \
        * jnp.dtype(cfg.dtype).itemsize


# what the host knows of a job's grouped-query layers, for the counters


def kv_key_blocks(layers: int, prompt_tokens: int, new: int, chunk: int,
                  capacity: int) -> tuple[tuple[int, int], tuple[int, int]]:
    """Of ``layers`` FULL layers: ((key blocks the prefill reads over a
    prompt's chunks, those of the whole capacity over the same chunks),
    (key blocks the decode's sweep reads over ``new - 1`` steps, those of
    the whole capacity))."""
    prefill = prefill_key_blocks(layers, prompt_tokens, chunk, capacity)
    block = prompt_key_block(capacity)
    steps = layers * (new - 1)
    return prefill, (steps * -(-prompt_tokens // block),
                     steps * -(-capacity // block))


def window_key_blocks(layers: int, prompt_tokens: int, chunk: int,
                      window: int) -> tuple[int, int]:
    """Of ``layers`` SLIDING layers: (key blocks of the local buffer up
    to a chunk's end, summed over a prompt's chunks; blocks of the whole
    buffer over the same chunks)."""
    block = window_key_block(window + chunk)
    starts = range(0, prompt_tokens, chunk)
    return (layers * sum(-(-(min(pos, window) + chunk) // block)
                         for pos in starts),
            layers * len(starts) * -(-(window + chunk) // block))


def window_pairs(heads: int, kv_heads: int, prompt_tokens: int, rows: int,
                 new: int, chunk: int, window: int
                 ) -> dict[str, tuple[int, int]]:
    """Query-key pairs of ONE sliding layer a head, (prefill, decode):
    ``visible`` = inside the window (a query at position p sees ``min(p
    + 1, window)`` keys); ``scored`` = what is computed for them, masked
    or not: in the prefill every key of every block the kernel steps
    (``stepped_pairs``, of the G heads' rows of a key-value head, so
    over G), in the decode the ``window + new`` slots (the prompt's
    window and the whole suffix) a row's step scores."""
    g = heads // kv_heads
    steps = new - 1

    def seen(first: int, count: int) -> int:
        """sum of min(p + 1, window) over p in [first, first + count)."""
        rising = max(min(first + count, window) - first, 0)
        return rising * (2 * first + rising + 1) // 2 \
            + (count - rising) * window

    scored = sum(stepped_pairs(chunk * g, g, min(pos, window),
                               window + chunk, window)
                 for pos in range(0, prompt_tokens, chunk)) // g
    return {"visible": (seen(0, prompt_tokens),
                        rows * seen(prompt_tokens, steps)),
            "scored": (scored, rows * steps * (window + new))}


# ---- experts ---------------------------------------------------------------


def n_held(cfg) -> int:
    return cfg.experts_held[1] - cfg.experts_held[0]


def held_experts_part(p, cfg, x, chosen, weight, valid):
    """The weighted outputs of the HELD experts among the chosen. The
    (token, expert) pairs that land on a held expert are sorted by
    expert and laid out in blocks of ``block`` rows, each block one
    expert's; a loop over the blocks in use (dynamic trip count) slices
    that expert's weights and computes the block. Returns (y (T, d),
    pairs held, distinct held experts hit)."""
    t, k = chosen.shape
    d = x.shape[-1]
    held_n = n_held(cfg)
    block = 128 if t >= 1024 else 8
    pairs = t * k
    max_blocks = min(held_n, pairs) + pairs // block
    local = chosen.reshape(-1) - cfg.experts_held[0]
    held = (local >= 0) & (local < held_n) & jnp.repeat(valid, k)
    local = jnp.where(held, local, held_n)          # the rest sort last
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    counts = jnp.zeros((held_n + 1,), jnp.int32).at[local].add(1)[:held_n]
    blocks_of = (counts + block - 1) // block
    ends = jnp.cumsum(blocks_of)
    first_block, n_blocks = ends - blocks_of, ends[-1]
    first_pair = jnp.cumsum(counts) - counts
    e_safe = jnp.minimum(sorted_e, held_n - 1)
    row = first_block[e_safe] * block \
        + (jnp.arange(pairs) - first_pair[e_safe])
    row = jnp.where(sorted_e < held_n, row, max_blocks * block)
    token_of_row = jnp.full((max_blocks * block,), t, jnp.int32).at[
        row].set((order // k).astype(jnp.int32), mode="drop")
    expert_of_block = jnp.searchsorted(
        ends, jnp.arange(max_blocks), side="right").astype(jnp.int32)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    experts = p["experts"]

    def body(i, out):
        e = jnp.minimum(expert_of_block[i], held_n - 1)
        rows = jax.lax.dynamic_slice_in_dim(token_of_row, i * block, block)
        xb = x_pad[rows]
        one = {name: jax.lax.dynamic_index_in_dim(w, e, keepdims=False)
               for name, w in experts.items()}
        return jax.lax.dynamic_update_slice_in_dim(
            out, swiglu(one, xb), i * block, axis=0)

    out = jax.lax.fori_loop(
        0, n_blocks, body,
        jnp.zeros((max_blocks * block + 1, d), x.dtype))
    row_of_pair = jnp.zeros((pairs,), jnp.int32).at[order].set(
        row.astype(jnp.int32))
    gathered = out[row_of_pair].reshape(t, k, d).astype(jnp.float32)
    w_held = jnp.where(held.reshape(t, k), weight, 0.0)
    y = jnp.einsum("tkd,tk->td", gathered, w_held, precision=HIGHEST)
    return y.astype(x.dtype), jnp.sum(held), jnp.sum(counts > 0)


def moe(p, cfg, x, route, valid=None):
    """x (..., d) -> (shared expert + held experts' part, stats).
    ``route(p, cfg, x (T, d))`` is the stack's router: (chosen experts
    (T, K) int32, their weights (T, K) float32) over ALL experts."""
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    if valid is None:
        valid = jnp.ones((flat.shape[0],), bool)
    chosen, weight = route(p, cfg, flat)
    y, held, hit = held_experts_part(p, cfg, flat, chosen, weight,
                                     valid.reshape(-1))
    y = y + swiglu(p["shared"], flat)
    stats = {"pairs": jnp.sum(valid) * cfg.num_experts_per_tok,
             "pairs_held": held, "experts_hit": hit}
    return y.reshape(*lead, -1), stats


def empty_stats():
    zero = jnp.zeros((), jnp.int32)
    return {"pairs": zero, "pairs_held": zero, "experts_hit": zero}


def mlp_block(layer, cfg, x, stats, route, valid=None):
    """x + MLP(RMSNorm(x)): the dense SwiGLU where the layer has one
    (no ``experts`` among its weights), the expert layer otherwise,
    whose counts are added to ``stats``."""
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    if "experts" not in layer["mlp"]:
        return x + swiglu(layer["mlp"], h), stats
    y, s = moe(layer["mlp"], cfg, h, route, valid)
    return x + y, {k: stats[k] + s[k].astype(jnp.int32) for k in stats}
