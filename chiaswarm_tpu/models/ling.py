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
    param_bytes,
    rms_norm,
    swiglu,
)
from chiaswarm_tpu.models.text_layers import proj as _proj


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


def mla_prefill(p, cfg: LingConfig, x, cache, pos):
    """x (B, T, d) at positions [pos, pos + T) -> (y, cache): a
    full-rank query, ``text_layers.latent_prefill``, the gate, W_o."""
    o, cache = text_layers.latent_prefill(
        p, cfg, x, _proj(x, p["wq"]), cache, pos,
        inv_freq=_rope_frequencies(cfg), scale=mla_scale(cfg))
    return _mla_out(p, cfg, x, o), cache


def prefill_key_blocks(cfg: LingConfig, prompt_tokens: int, chunk: int,
                       capacity: int) -> tuple[int, int]:
    """(key blocks ``mla_prefill`` reads over a prompt's chunks, key
    blocks of the whole capacity over the same chunks), summed over the
    latent-attention layers: host integers, for the counter."""
    return text_layers.prefill_key_blocks(len(cfg.mla_layers),
                                          prompt_tokens, chunk, capacity)


def mla_decode(p, cfg: LingConfig, x, prompt_cache, prompt_len, suffix,
               step):
    """One new token a row, absorbed form (``text_layers.latent_decode``):
    x (R, 1, d) -> (y, suffix)."""
    o, suffix = text_layers.latent_decode(
        p, cfg, x, _proj(x, p["wq"]), prompt_cache, prompt_len, suffix,
        step, inv_freq=_rope_frequencies(cfg), scale=mla_scale(cfg))
    return _mla_out(p, cfg, x, o), suffix


# ---- experts (the layer is text_layers') -----------------------------------


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


def moe(p, cfg: LingConfig, x, valid=None):
    """x (..., d) -> (shared expert + held experts' part, stats)."""
    return text_layers.moe(p, cfg, x, route, valid)


# ---- the stack -------------------------------------------------------------


def empty_prefill_caches(cfg: LingConfig, capacity: int):
    """One row's caches before its first token: a recurrent state and
    conv tails per KDA layer, a latent cache of ``capacity`` entries per
    MLA layer."""
    return {"kda": [kda_empty_cache(cfg, 1) for _ in cfg.kda_layers],
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
            y, kda[j] = kda_prefill(layer["attn"], cfg, h, kda[j], n_valid)
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
            y, kda[j] = kda_decode(layer["attn"], cfg, h, kda[j])
        x, stats = text_layers.mlp_block(layer, cfg, x + y, stats, route)
    caches = {"kda": kda, "prompt": caches["prompt"], "suffix": suffix}
    return text_layers.logits_of(params, cfg, x[:, 0]), caches, stats


def cache_bytes(cfg: LingConfig, rows: int, capacity: int,
                max_new: int) -> dict[str, int]:
    """Bytes of the two kinds of cache a decode of ``rows`` rows holds."""
    item = jnp.dtype(cfg.dtype).itemsize
    h, dk = cfg.num_attention_heads, cfg.head_dim
    recurrent = len(cfg.kda_layers) * rows * (
        h * dk * dk * 4
        + 3 * (cfg.short_conv_kernel_size - 1) * h * dk * item)
    return {"recurrent": recurrent,
            "latent": text_layers.latent_cache_bytes(
                cfg, len(cfg.mla_layers), rows, capacity, max_new)}


def job_counts(cfg: LingConfig, prompt_tokens: int, rows: int, new: int,
               chunk: int, capacity: int) -> dict[str, Any]:
    """What the host knows of one job's two programs, for the counters
    (``pipelines/text.py::TextPipeline._count``): key blocks the causal
    kernel reads and leaves in the prefill and in the decode, query-key
    pairs a head scores by phase, the prefill kernel's grid steps by
    kind, the delta-rule prefill's sub-blocks by form, the expert
    layers."""
    layers = len(cfg.mla_layers)
    return {
        "key_blocks": prefill_key_blocks(cfg, prompt_tokens, chunk,
                                         capacity),
        "block_steps": text_layers.prefill_block_steps(
            layers, cfg.num_attention_heads, 1, prompt_tokens, chunk,
            capacity),
        "decode_key_blocks": text_layers.decode_key_blocks(
            layers, prompt_tokens, new, capacity),
        "attention_pairs": text_layers.attention_pairs(
            layers, prompt_tokens, rows, new),
        "kda_blocks": kda_blocks(cfg, prompt_tokens, chunk),
        "expert_layers": sum(cfg.is_moe(i)
                             for i in range(cfg.num_hidden_layers))}
