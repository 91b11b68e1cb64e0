"""Ling-3.0-flash-class decoder: delta-rule linear attention (KDA) beside
multi-head latent attention (MLA), sigmoid-routed sparse experts held in
part — the language model behind the ``txt2txt`` workflow
(pipelines/text.py, workloads/text.py).

Three layer kinds the UNet families do not have:

- **KDA** — a gated delta-rule recurrence with a per-channel decay. Per
  head a float32 state ``S`` (d_k x d_v):
  ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``,
  ``o_t = S_t^T q_t``. Prefill computes it chunkwise (the UT form of the
  delta rule inside a chunk: one unit-lower-triangular solve, the state
  carried between chunks); decode is the recurrence itself. The decays
  inside a chunk are applied pairwise (``exp(G_t - G_j)``, never a ratio
  of two exponentials), so any gate in (e^-5, 1) is exact.
- **MLA** — latent attention with a compressed cache (512 latent + 64
  rotary values a token) and two compute paths: prefill up-projects keys
  and values and goes through ``ops.attention`` (causal), decode folds
  the key up-projection into the query and the value up-projection
  after the softmax (the absorbed form), against the latents directly.
- **Experts** — the layer is told which experts it holds
  (``experts_held``), routes over all of them, and computes its own
  experts' part for the tokens routed to them (plus the shared expert);
  what the absent experts would add is left out. Tokens are grouped by
  expert into blocks and a loop with a dynamic trip count walks the
  blocks in use, so a step reads the weights of the experts that were
  hit and no others, and no token is ever dropped.

Functional style (a dict pytree of arrays, plain functions): the expert
weights are stacked (experts, in, out) and sliced by a traced index,
which flax modules would only obscure. Weights and activations in the
configuration's dtype (bfloat16 served), router and recurrent state in
float32 with ``Precision.HIGHEST`` (a float32 product is one bfloat16
pass on a TPU otherwise).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from chiaswarm_tpu.ops.attention import attention
from chiaswarm_tpu.ops.causal_flash_attention import key_block

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


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

    def is_mla(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

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


#: the CPU tests' size: 2 dense + one whole period, 16 experts in 4
#: groups of which 4 are held
LING_TINY = LingConfig(
    vocab_size=96, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=2, head_dim=16, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=16,
    experts_held=(0, 4), num_experts_per_tok=4, n_group=4, topk_group=2,
    dtype="float32", kda_chunk=4)


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
                                     (cfg.n_held,)),
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


def param_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))


# ---- pieces shared by every layer ----------------------------------------


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def _proj(x, w):
    """x @ w in the activations' dtype, accumulated in float32."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(p, x):
    return _proj(jax.nn.silu(_proj(x, p["gate"])) * _proj(x, p["up"]),
                 p["down"])


def rope(x, positions, theta: float):
    """Rotate-half RoPE over the last axis of ``x`` (..., T, D) at
    integer ``positions`` (T,), in float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# ---- KDA: gated delta-rule linear attention -----------------------------


def _short_conv(pre, tail, weight, n_valid):
    """Depthwise causal conv over time then SiLU. ``pre`` (B, T, C) new
    pre-conv values, ``tail`` (B, K-1, C) the last ones before them.
    Returns (out (B, T, C), the tail after ``n_valid`` new tokens)."""
    k = weight.shape[0]
    t = pre.shape[1]
    seq = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    out = sum(seq[:, i:i + t].astype(jnp.float32)
              * weight[i].astype(jnp.float32) for i in range(k))
    new_tail = jax.lax.dynamic_slice_in_dim(seq, n_valid, k - 1, axis=1)
    return jax.nn.silu(out).astype(pre.dtype), new_tail


def kda_inputs(p, cfg: LingConfig, x, tails, n_valid):
    """The recurrence's operands from the normed input ``x`` (B, T, d):
    q, k, v (B, T, H, D) float32, log-decay g (B, T, H, D) and write
    strength b (B, T, H), both float32 and neutral (0) past ``n_valid``;
    plus the conv tails after the valid tokens."""
    b_, t, _ = x.shape
    h, dk = cfg.num_attention_heads, cfg.head_dim
    outs, new_tails = [], []
    for name, tail in zip("qkv", tails):
        y, nt = _short_conv(_proj(x, p[f"w{name}"]), tail,
                            p[f"conv_{name}"], n_valid)
        outs.append(y.astype(jnp.float32).reshape(b_, t, h, dk))
        new_tails.append(nt)
    q, k, v = outs

    def l2(z):
        return z * jax.lax.rsqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

    q = l2(q) * dk ** -0.5
    k = l2(k)
    gate_in = _proj(x, p["wa"]).astype(jnp.float32) + p["dt_bias"]
    gate_in = gate_in.reshape(b_, t, h, dk) \
        * jnp.exp(p["a_log"])[None, None, :, None]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(gate_in)
    beta = jax.nn.sigmoid(_proj(x, p["wb"]).astype(jnp.float32))
    valid = (jnp.arange(t) < n_valid)[None, :]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, g, beta, tuple(new_tails)


def kda_recurrent_step(q, k, v, g, beta, state):
    """One token of the recurrence. q, k, v, g (B, H, D), beta (B, H),
    state (B, H, Dk, Dv) float32 -> (o (B, H, Dv), state)."""
    state = state * jnp.exp(g)[..., None]
    pred = jnp.einsum("bhk,bhkv->bhv", k, state, precision=HIGHEST)
    u = beta[..., None] * (v - pred)
    state = state + k[..., None] * u[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, state, precision=HIGHEST), state


def kda_block(chunk: int) -> int:
    """Tokens a side of the sub-blocks ``kda_chunked`` cuts a chunk's two
    matrices into: 16, or the whole chunk where 16 does not divide it."""
    return chunk if chunk % 16 else 16


def kda_chunked(q, k, v, g, beta, state, chunk: int):
    """The same recurrence over T tokens, ``chunk`` at a time (T a
    multiple of it). Inside a chunk, with G the running sum of g:
    ``(I + Diag(b) A) U = Diag(b) (V - (K*e^G) S0)`` with
    ``A_tj = sum_c k_tc k_jc e^(G_tc - G_jc)`` for j < t, then
    ``O = (Q*e^G) S0 + B U`` with B the same sum over q_t k_j, j <= t,
    and ``S = e^(G_last) S0 + (K*e^(G_last - G))^T U``.

    A and B are built in row blocks of ``kda_block(chunk)`` tokens. On a
    diagonal sub-block the decays are pairwise, ``e^(G_t - G_j)``. Left
    of it, with r the block's first row, they are a product:
    ``[k_t e^(G_t - G_r)] . [k_j e^(G_r - G_j)]``. G never rises, so for
    j < r <= t both exponents are <= 0: nothing overflows, and a factor
    that underflows stands for a weight under e^-87."""
    b_, t, h, dk = q.shape
    n = t // chunk
    sub = kda_block(chunk)
    nb = chunk // sub

    def split(z):  # (B, T, H, ...) -> (N, B, H, C, ...)
        z = z.reshape(b_, n, chunk, *z.shape[2:])
        return jnp.moveaxis(jnp.swapaxes(z, 2, 3), 1, 0)

    qs, ks, vs, gs = (split(z) for z in (q, k, v, g))
    bs = split(beta)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    lower_sub = jnp.tril(jnp.ones((sub, sub), bool))
    # keys before each row block's first row
    earlier = jnp.arange(chunk)[None, :] < sub * jnp.arange(nb)[:, None]

    def blocks(z):  # (B, H, C, D) -> (B, H, C/sub, sub, D)
        return z.reshape(b_, h, nb, sub, dk)

    def in_place(diag):  # (B, H, C/sub, sub, sub) -> (B, H, C/sub, sub, C)
        return jnp.stack([
            jnp.pad(diag[:, :, i], ((0, 0),) * 3
                    + ((i * sub, chunk - (i + 1) * sub),))
            for i in range(nb)], axis=2)

    def body(state, xs):
        qc, kc, vc, gc, bc = xs                    # (B, H, C, D) / (B, H, C)
        big_g = jnp.cumsum(gc, axis=2)
        g_blk, q_blk, k_blk = blocks(big_g), blocks(qc), blocks(kc)
        # diagonal sub-blocks: pairwise decays, masked BEFORE the
        # exponential: for j > t the difference is positive and can
        # overflow
        diff = g_blk[:, :, :, :, None, :] - g_blk[:, :, :, None, :, :]
        decay = jnp.exp(jnp.where(lower_sub[:, :, None], diff, -jnp.inf))
        pair = k_blk[:, :, :, None, :, :] * decay
        a_mat = in_place(jnp.sum(k_blk[:, :, :, :, None, :] * pair, axis=-1))
        b_mat = in_place(jnp.sum(q_blk[:, :, :, :, None, :] * pair, axis=-1))
        if nb > 1:
            # left of them: rows (A's keys and B's queries in one product)
            # and earlier keys rescaled to the row block's first row, the
            # keys masked BEFORE the exponential too
            first = g_blk[:, :, :, :1, :]
            rows = jnp.stack([k_blk, q_blk], axis=2) \
                * jnp.exp(g_blk - first)[:, :, None]
            right = kc[:, :, None] * jnp.exp(jnp.where(
                earlier[:, :, None], first - big_g[:, :, None], -jnp.inf))
            left = jnp.einsum("bhxitd,bhijd->bhxitj", rows, right,
                              precision=HIGHEST)
            a_mat, b_mat = a_mat + left[:, :, 0], b_mat + left[:, :, 1]
        a_mat, b_mat = (m.reshape(b_, h, chunk, chunk) for m in (a_mat, b_mat))
        e_g = jnp.exp(big_g)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhck,bhkv->bhcv", kc * e_g, state, precision=HIGHEST))
        system = jnp.where(strict, bc[..., None] * a_mat, 0.0) \
            + jnp.eye(chunk, dtype=jnp.float32)
        u = jax.lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhck,bhkv->bhcv", qc * e_g, state,
                       precision=HIGHEST) \
            + jnp.einsum("bhcj,bhjv->bhcv", b_mat, u, precision=HIGHEST)
        last = big_g[:, :, -1:, :]
        state = state * jnp.exp(last)[:, :, 0, :, None] + jnp.einsum(
            "bhck,bhcv->bhkv", kc * jnp.exp(last - big_g), u,
            precision=HIGHEST)
        return state, o

    state, o = jax.lax.scan(body, state, (qs, ks, vs, gs, bs))
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3)      # (B, N, C, H, Dv)
    return o.reshape(b_, t, h, -1), state


def kda_blocks(cfg: LingConfig, prompt_tokens: int, chunk: int
               ) -> tuple[int, int]:
    """(sub-blocks of the in-chunk matrices ``kda_chunked`` builds from
    pairwise decays, sub-blocks it builds as products), summed over the
    KDA layers, a prompt's prefill chunks and their sub-chunks: host
    integers, for the counter."""
    sub_chunk = min(cfg.kda_chunk, chunk)
    nb = sub_chunk // kda_block(sub_chunk)
    n = len(cfg.kda_layers) * -(-prompt_tokens // chunk) \
        * (chunk // sub_chunk)
    return n * nb, n * nb * (nb - 1) // 2


def _kda_out(p, cfg: LingConfig, x, o):
    """Per-head RMSNorm of the read-out, the sigmoid output gate, W_o."""
    b_, t = x.shape[:2]
    o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
    gate = jax.nn.sigmoid(_proj(x, p["wg"]).astype(jnp.float32))
    o = (o * gate.reshape(o.shape)).astype(x.dtype)
    return _proj(o.reshape(b_, t, -1), p["wo"])


def kda_prefill(p, cfg: LingConfig, x, cache, n_valid):
    """x (B, T, d) -> (y, cache): cache = (state, (tail_q, tail_k,
    tail_v))."""
    state, tails = cache
    q, k, v, g, beta, tails = kda_inputs(p, cfg, x, tails, n_valid)
    o, state = kda_chunked(q, k, v, g, beta, state,
                           min(cfg.kda_chunk, x.shape[1]))
    return _kda_out(p, cfg, x, o), (state, tails)


def kda_decode(p, cfg: LingConfig, x, cache):
    """x (B, 1, d): the recurrence itself."""
    state, tails = cache
    q, k, v, g, beta, tails = kda_inputs(p, cfg, x, tails, 1)
    o, state = kda_recurrent_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state)
    return _kda_out(p, cfg, x, o[:, None]), (state, tails)


def kda_empty_cache(cfg: LingConfig, batch: int):
    h, dk = cfg.num_attention_heads, cfg.head_dim
    tail = jnp.zeros((batch, cfg.short_conv_kernel_size - 1, h * dk),
                     jnp.dtype(cfg.dtype))
    return (jnp.zeros((batch, h, dk, dk), jnp.float32), (tail,) * 3)


# ---- MLA: latent attention ------------------------------------------------


def _mla_query_and_latent(p, cfg: LingConfig, x, positions):
    """q_nope (B, T, H, Dn), q_rope (B, T, H, Dr) and the cache entry
    (B, T, latent + Dr): the normed latent and the rotated shared key."""
    b_, t, _ = x.shape
    h = cfg.num_attention_heads
    q = _proj(x, p["wq"]).reshape(b_, t, h, -1)
    q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    q_r = rope(jnp.swapaxes(q_r, 1, 2), positions, cfg.rope_theta)
    q_r = jnp.swapaxes(q_r, 1, 2).astype(x.dtype)
    ckr = _proj(x, p["wdkv"])
    c = rms_norm(ckr[..., :cfg.kv_lora_rank], p["kv_norm"],
                 cfg.rms_norm_eps)
    k_r = rope(ckr[..., cfg.kv_lora_rank:], positions,
               cfg.rope_theta).astype(x.dtype)
    return q_n, q_r, jnp.concatenate([c, k_r], axis=-1)


def _mla_out(p, cfg: LingConfig, x, o):
    """One sigmoid gate a head, then W_o. o (B, T, H, Dv)."""
    b_, t = x.shape[:2]
    gate = jax.nn.sigmoid(_proj(x, p["wgate"]).astype(jnp.float32))
    o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    return _proj(o.reshape(b_, t, -1), p["wo"])


def mla_scale(cfg: LingConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_prefill(p, cfg: LingConfig, x, cache, pos):
    """x (B, T, d) at positions [pos, pos + T); ``cache`` (B, S, latent
    + Dr) holds every earlier token's entry. Up-projects the latents to
    keys and values block by block, as far as the cache is written and
    no further, and attends causally (``ops.attention``) over the same
    blocks; the rotary key goes in as it lies in the cache, one for all
    heads."""
    b_, t, _ = x.shape
    h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    q_n, q_r, entry = _mla_query_and_latent(p, cfg, x,
                                            pos + jnp.arange(t))
    cache = jax.lax.dynamic_update_slice_in_dim(cache, entry, pos, axis=1)
    s = cache.shape[1]
    block = key_block(t, s)
    wukv = p["wukv"].reshape(rank, h, -1)
    w_uk, w_uv = (w.reshape(rank, -1) for w in (
        wukv[..., :cfg.qk_nope_head_dim], wukv[..., cfg.qk_nope_head_dim:]))

    def up_project(i, kv):
        latents = jax.lax.dynamic_slice_in_dim(
            cache, i * block, block, axis=1)[..., :rank]
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                whole, _proj(latents, w), i * block, axis=1)
            for whole, w in zip(kv, (w_uk, w_uv)))

    # blocks past the written length stay zero and are never read
    k_n, v = jax.lax.fori_loop(
        0, (pos + t + block - 1) // block, up_project,
        tuple(jnp.zeros((b_, s, w.shape[1]), x.dtype)
              for w in (w_uk, w_uv)))
    o = attention(q_n, k_n.reshape(b_, s, h, -1), v.reshape(b_, s, h, -1),
                  scale=mla_scale(cfg), causal=True, q_offset=pos,
                  shared_key=(q_r, cache[..., rank:]))
    return _mla_out(p, cfg, x, o), cache


def prefill_key_blocks(cfg: LingConfig, prompt_tokens: int, chunk: int,
                       capacity: int) -> tuple[int, int]:
    """(key blocks ``mla_prefill`` reads over a prompt's chunks, key
    blocks of the whole capacity over the same chunks), summed over the
    latent-attention layers: host integers, for the counter."""
    block = key_block(chunk, capacity)
    starts = range(0, prompt_tokens, chunk)
    layers = len(cfg.mla_layers)
    return (layers * sum(-(-(pos + chunk) // block) for pos in starts),
            layers * len(starts) * -(-capacity // block))


def mla_decode(p, cfg: LingConfig, x, prompt_cache, prompt_len, suffix,
               step):
    """The absorbed form for one new token a row. x (R, 1, d);
    ``prompt_cache`` (1, S, W) is shared by the rows (the first
    ``prompt_len`` entries are valid), ``suffix`` (R, N, W) is each
    row's own (entries [0, step] valid after this call's write)."""
    r = x.shape[0]
    h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    position = (prompt_len + step)[None]
    q_n, q_r, entry = _mla_query_and_latent(p, cfg, x, position)
    suffix = jax.lax.dynamic_update_slice_in_dim(suffix, entry, step, axis=1)
    wukv = p["wukv"].reshape(rank, h, -1)
    w_uk, w_uv = (wukv[..., :cfg.qk_nope_head_dim],
                  wukv[..., cfg.qk_nope_head_dim:])
    q_abs = jnp.einsum("rhd,chd->rhc", q_n[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    q_all = jnp.concatenate([q_abs, q_r[:, 0]], axis=-1)      # (R, H, W)
    shared = prompt_cache[0]
    s_prompt = jnp.einsum("rhw,sw->rhs", q_all, shared,
                          preferred_element_type=jnp.float32)
    s_own = jnp.einsum("rhw,rnw->rhn", q_all, suffix,
                       preferred_element_type=jnp.float32)
    s_prompt = jnp.where(jnp.arange(shared.shape[0]) < prompt_len,
                         s_prompt, NEG_INF)
    s_own = jnp.where(jnp.arange(suffix.shape[1]) <= step, s_own, NEG_INF)
    weights = jax.nn.softmax(
        jnp.concatenate([s_prompt, s_own], -1) * mla_scale(cfg), axis=-1)
    weights = weights.astype(x.dtype)
    n_prompt = shared.shape[0]
    o_lat = jnp.einsum("rhs,sc->rhc", weights[..., :n_prompt],
                       shared[:, :rank],
                       preferred_element_type=jnp.float32) \
        + jnp.einsum("rhn,rnc->rhc", weights[..., n_prompt:],
                     suffix[..., :rank], preferred_element_type=jnp.float32)
    o = jnp.einsum("rhc,chd->rhd", o_lat.astype(x.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    return _mla_out(p, cfg, x, o[:, None].astype(x.dtype)), suffix


# ---- experts ---------------------------------------------------------------


def route(p, cfg: LingConfig, x):
    """x (T, d) -> (chosen experts (T, K) int32, their weights (T, K)
    float32): sigmoid scores over ALL experts; chosen by score + bias,
    the best ``topk_group`` groups by the sum of their top two, then the
    best K inside them; weights are the scores of the chosen, summing to
    one, times the scaling factor."""
    t = x.shape[0]
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p["router"],
                                    precision=HIGHEST))
    choose = scores + p["router_bias"]
    groups = choose.reshape(t, cfg.n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, cfg.topk_group)
    keep = jnp.zeros((t, cfg.n_group), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    masked = jnp.where(keep[..., None], groups, -jnp.inf).reshape(t, -1)
    _, chosen = jax.lax.top_k(masked, cfg.num_experts_per_tok)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / jnp.sum(weight, -1, keepdims=True) \
        * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), weight


def held_experts_part(p, cfg: LingConfig, x, chosen, weight, valid):
    """The weighted outputs of the HELD experts among the chosen. The
    (token, expert) pairs that land on a held expert are sorted by
    expert and laid out in blocks of ``block`` rows, each block one
    expert's; a loop over the blocks in use (dynamic trip count) slices
    that expert's weights and computes the block. Returns (y (T, d),
    pairs held, distinct held experts hit)."""
    t, k = chosen.shape
    d = x.shape[-1]
    n_held = cfg.n_held
    block = 128 if t >= 1024 else 8
    pairs = t * k
    max_blocks = min(n_held, pairs) + pairs // block
    local = chosen.reshape(-1) - cfg.experts_held[0]
    held = (local >= 0) & (local < n_held) & jnp.repeat(valid, k)
    local = jnp.where(held, local, n_held)          # the rest sort last
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    counts = jnp.zeros((n_held + 1,), jnp.int32).at[local].add(1)[:n_held]
    blocks_of = (counts + block - 1) // block
    ends = jnp.cumsum(blocks_of)
    first_block, n_blocks = ends - blocks_of, ends[-1]
    first_pair = jnp.cumsum(counts) - counts
    e_safe = jnp.minimum(sorted_e, n_held - 1)
    row = first_block[e_safe] * block \
        + (jnp.arange(pairs) - first_pair[e_safe])
    row = jnp.where(sorted_e < n_held, row, max_blocks * block)
    token_of_row = jnp.full((max_blocks * block,), t, jnp.int32).at[
        row].set((order // k).astype(jnp.int32), mode="drop")
    expert_of_block = jnp.searchsorted(
        ends, jnp.arange(max_blocks), side="right").astype(jnp.int32)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    experts = p["experts"]

    def body(i, out):
        e = jnp.minimum(expert_of_block[i], n_held - 1)
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


def moe(p, cfg: LingConfig, x, valid=None):
    """x (..., d) -> (shared expert + held experts' part, stats)."""
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


# ---- the stack -------------------------------------------------------------


def empty_stats():
    zero = jnp.zeros((), jnp.int32)
    return {"pairs": zero, "pairs_held": zero, "experts_hit": zero}


def _mlp(layer, cfg: LingConfig, i: int, x, stats, valid=None):
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    if not cfg.is_moe(i):
        return x + swiglu(layer["mlp"], h), stats
    y, s = moe(layer["mlp"], cfg, h, valid)
    return x + y, {k: stats[k] + s[k].astype(jnp.int32) for k in stats}


def logits_of(params, cfg: LingConfig, x):
    """Hidden states (..., d) -> float32 logits over the slice held."""
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def empty_prefill_caches(cfg: LingConfig, capacity: int):
    """One row's caches before its first token: a recurrent state and
    conv tails per KDA layer, a latent cache of ``capacity`` entries per
    MLA layer."""
    return {"kda": [kda_empty_cache(cfg, 1) for _ in cfg.kda_layers],
            "mla": [jnp.zeros((1, capacity, cfg.latent_width),
                              jnp.dtype(cfg.dtype))
                    for _ in cfg.mla_layers]}


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
            y, kda[j] = kda_prefill(layer["attn"], cfg, h, kda[j], n_valid)
        x, stats = _mlp(layer, cfg, i, x + y, stats, valid[None])
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)[:, 0]
    return logits_of(params, cfg, last), {"kda": kda, "mla": mla}, stats


def decode_caches(cfg: LingConfig, caches, rows: int, max_new: int):
    """The prompt's caches as ``rows`` rows start from them: recurrent
    state and conv tails broadcast (every row writes its own from the
    first token on), the prompt's latents shared, an empty suffix of
    ``max_new`` latents a row."""
    def spread(x):
        return jnp.broadcast_to(x, (rows,) + x.shape[1:])

    return {"kda": jax.tree.map(spread, caches["kda"]),
            "prompt": caches["mla"],
            "suffix": [jnp.zeros((rows, max_new, cfg.latent_width),
                                 c.dtype) for c in caches["mla"]]}


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
            y, kda[j] = kda_decode(layer["attn"], cfg, h, kda[j])
        x, stats = _mlp(layer, cfg, i, x + y, stats)
    caches = {"kda": kda, "prompt": caches["prompt"], "suffix": suffix}
    return logits_of(params, cfg, x[:, 0]), caches, stats


def cache_bytes(cfg: LingConfig, rows: int, capacity: int,
                max_new: int) -> dict[str, int]:
    """Bytes of the two kinds of cache a decode of ``rows`` rows holds."""
    item = jnp.dtype(cfg.dtype).itemsize
    h, dk = cfg.num_attention_heads, cfg.head_dim
    recurrent = len(cfg.kda_layers) * rows * (
        h * dk * dk * 4
        + 3 * (cfg.short_conv_kernel_size - 1) * h * dk * item)
    latent = len(cfg.mla_layers) * (capacity + rows * max_new) \
        * cfg.latent_width * item
    return {"recurrent": recurrent, "latent": latent}
