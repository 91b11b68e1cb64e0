"""Layers the text stacks share (models/ling.py, models/deepseek.py,
models/laguna.py, models/solar.py, models/lfm2.py): the norm, the SwiGLU
and the rotary pieces (YaRN among them), the delta rule with its
recurrent state, the gated short convolution with its conv tail, latent
attention (MLA) with its compressed cache, attention over plain keys and
values with its two kinds of cache, and the expert layer of which a chip
holds a part. One implementation of each; a stack tells them what
differs.

- **The delta rule.** Gated delta-rule linear attention with a decay
  per channel (KDA). Per head a float32 state ``S`` (d_k x d_v):
  ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``,
  ``o_t = S_t^T q_t``, with q, k, v through a short causal conv and SiLU,
  q and k l2-normed, the read-out RMS-normed a head and gated. Prefill
  computes it chunkwise (the UT form: one unit-lower-triangular solve a
  chunk, the state carried between chunks), decode is the recurrence
  itself. A stack's configuration gives ``DeltaRule`` its two options:
  the write strength ``b = b_max sigmoid(W_b x)`` with ``b_max`` 1, or 2
  where the transition may have negative eigenvalues (in (-1, 1]); and
  the decay's form, a gate bounded below (``log a = lower sigmoid(e^A
  (W_a x + dt_bias))``) or the softplus form (``log a = -e^A
  softplus(W_a x + dt_bias)``), either a non-positive log-decay. Each
  gate projection is one matrix or a low-rank pair, as the stack's
  weights hold it (``wa`` / ``wa_down`` and ``wa_up``; ``wg`` likewise).
- **The gated short convolution** (LFM2's mixer): ``[B | C | u] = x
  W_in``, ``y = (C * conv(B * u)) W_out`` with a depthwise causal conv of
  a few taps and no activation. Its only state is the last taps - 1
  values of ``B * u`` a channel (the conv tail), carried across a
  prefill's chunks and rolled by each decode step: the delta rule's short
  conv with the activation left out.

- **Latent attention.** The cache holds 512 normed latent + 64 rotated
  key values a token (``kv_lora_rank + qk_rope_head_dim``), one entry for
  all heads. Two compute paths: prefill hands the latents and W_ukv to
  the causal sweep, which up-projects each head's keys and values block
  by block where it reads them
  (``ops.attention.latent_prefill_attention``), decode folds the key
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
  part; None where the model encodes no position in this layer), their
  amplitude, the softmax scale, where the model norms each head's query
  and key before the rotary the norm's eps (the gains ``q_norm`` /
  ``k_norm`` among the layer's weights) and, for a sliding layer, the
  window. A FULL layer's cache has the capacity's slots; its prefill
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
  tokens routed to them (plus the shared expert, where the layer has
  one); what the absent experts would add is left out. Tokens are
  grouped by expert into blocks and a loop with a dynamic trip count
  walks the blocks in use,
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

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from chiaswarm_tpu.ops.attention import (
    attention,
    latent_prefill_attention,
    shared_latent_attention,
    shared_prompt_attention,
)
from chiaswarm_tpu.obs.trace import scope
from chiaswarm_tpu.ops.causal_flash_attention import (
    block_steps,
    prompt_key_block,
    shared_key_block,
    stepped_pairs,
    up_projected_blocks,
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


@scope("head")
def logits_of(params, cfg, x):
    """Hidden states (..., d) -> float32 logits over the slice held: by
    the head, or where the weights hold none (a head tied to the
    embedding) by the embedding's (V, d) rows, read where they lie."""
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if "head" in params:
        return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)
    return jnp.einsum("...d,vd->...v", h, params["embed"],
                      preferred_element_type=jnp.float32)


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


# ---- the delta rule: gated delta-rule linear attention --------------------


@dataclasses.dataclass(frozen=True)
class DeltaRule:
    """What a stack's configuration tells the delta-rule core."""

    heads: int
    head_dim: int                       # d_k = d_v
    conv: int                           # the short conv's kernel size
    chunk: int                          # tokens a UT-form chunk holds
    eps: float                          # the read-out's RMSNorm
    #: the bounded gate's floor (``log a = lower_bound x sigmoid(...)``);
    #: None: the softplus form ``log a = -e^A softplus(...)``
    lower_bound: float | None = -5.0
    #: b up to 2 (the transition's eigenvalues in (-1, 1]) where True,
    #: up to 1 otherwise
    allow_neg_eigval: bool = False


def short_conv(pre, tail, weight, n_valid, activation=jax.nn.silu):
    """Depthwise causal conv over time, then ``activation`` (None: none).
    ``pre`` (B, T, C) new pre-conv values, ``tail`` (B, K-1, C) the last
    ones before them; tap i of ``weight`` (K, C) multiplies the value
    K-1-i tokens back. Returns (out (B, T, C), the tail after ``n_valid``
    new tokens)."""
    k = weight.shape[0]
    t = pre.shape[1]
    seq = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    out = sum(seq[:, i:i + t].astype(jnp.float32)
              * weight[i].astype(jnp.float32) for i in range(k))
    new_tail = jax.lax.dynamic_slice_in_dim(seq, n_valid, k - 1, axis=1)
    if activation is not None:
        out = activation(out)
    return out.astype(pre.dtype), new_tail


def gate_proj(p, name: str, x):
    """x @ W for the gate ``name``: one matrix (``p[name]``) or a
    low-rank pair (``p[name + "_down"]`` then ``p[name + "_up"]``)."""
    if name in p:
        return proj(x, p[name])
    return proj(proj(x, p[f"{name}_down"]), p[f"{name}_up"])


def kda_inputs(p, rule: DeltaRule, x, tails, n_valid):
    """The recurrence's operands from the normed input ``x`` (B, T, d):
    q, k, v (B, T, H, D) float32, log-decay g (B, T, H, D) and write
    strength b (B, T, H), both float32 and neutral (0) past ``n_valid``;
    plus the conv tails after the valid tokens."""
    b_, t, _ = x.shape
    h, dk = rule.heads, rule.head_dim
    outs, new_tails = [], []
    for name, tail in zip("qkv", tails):
        y, nt = short_conv(proj(x, p[f"w{name}"]), tail, p[f"conv_{name}"],
                           n_valid)
        outs.append(y.astype(jnp.float32).reshape(b_, t, h, dk))
        new_tails.append(nt)
    q, k, v = outs

    def l2(z):
        return z * jax.lax.rsqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

    q = l2(q) * dk ** -0.5
    k = l2(k)
    gate_in = gate_proj(p, "wa", x).astype(jnp.float32) + p["dt_bias"]
    gate_in = gate_in.reshape(b_, t, h, dk)
    rate = jnp.exp(p["a_log"])[None, None, :, None]
    if rule.lower_bound is None:
        g = -rate * jax.nn.softplus(gate_in)
    else:
        g = rule.lower_bound * jax.nn.sigmoid(gate_in * rate)
    beta = jax.nn.sigmoid(proj(x, p["wb"]).astype(jnp.float32))
    if rule.allow_neg_eigval:
        beta = 2.0 * beta
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
    ``[k_t e^(G_t - G_r)] . [k_j e^(G_r - G_j)]``. G never rises (any
    log-decay <= 0: the bounded gate's and the softplus form's alike), so
    for j < r <= t both exponents are <= 0: nothing overflows, and a
    factor that underflows stands for a weight under e^-87. ``b`` only
    scales the strictly lower rows of the system, which stays unit
    lower-triangular whatever b is (up to 2 where the transition has
    negative eigenvalues): U is each token's ``b_t (v_t - S_{t-1}^T
    k_t)``, bounded as the state is."""
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


def kda_out(p, rule: DeltaRule, x, o):
    """Per-head RMSNorm of the read-out, the sigmoid output gate, W_o."""
    b_, t = x.shape[:2]
    o = rms_norm(o, p["o_norm"], rule.eps)
    gate = jax.nn.sigmoid(gate_proj(p, "wg", x).astype(jnp.float32))
    o = (o * gate.reshape(o.shape)).astype(x.dtype)
    return proj(o.reshape(b_, t, -1), p["wo"])


@scope("recurrence")
def kda_prefill(p, rule: DeltaRule, x, cache, n_valid):
    """x (B, T, d) -> (y, cache): cache = (state, (tail_q, tail_k,
    tail_v))."""
    state, tails = cache
    q, k, v, g, beta, tails = kda_inputs(p, rule, x, tails, n_valid)
    o, state = kda_chunked(q, k, v, g, beta, state,
                           min(rule.chunk, x.shape[1]))
    return kda_out(p, rule, x, o), (state, tails)


@scope("recurrence")
def kda_decode(p, rule: DeltaRule, x, cache):
    """x (B, 1, d): the recurrence itself."""
    state, tails = cache
    q, k, v, g, beta, tails = kda_inputs(p, rule, x, tails, 1)
    o, state = kda_recurrent_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state)
    return kda_out(p, rule, x, o[:, None]), (state, tails)


def kda_empty_cache(rule: DeltaRule, batch: int, dtype):
    """(state (B, H, D, D) float32, three conv tails (B, K-1, H x D))."""
    h, dk = rule.heads, rule.head_dim
    tail = jnp.zeros((batch, rule.conv - 1, h * dk), jnp.dtype(dtype))
    return (jnp.zeros((batch, h, dk, dk), jnp.float32), (tail,) * 3)


def kda_state_bytes(rule: DeltaRule, item: int) -> int:
    """Bytes of one row's recurrent state and conv tails in one layer."""
    h, dk = rule.heads, rule.head_dim
    return h * dk * dk * 4 + 3 * (rule.conv - 1) * h * dk * item


# what the host knows of a job's delta-rule layers, for the counters


def kda_blocks(rule: DeltaRule, layers: int, prompt_tokens: int,
               chunk: int) -> tuple[int, int]:
    """(sub-blocks of the in-chunk matrices ``kda_chunked`` builds from
    pairwise decays, sub-blocks it builds as products), summed over
    ``layers`` delta-rule layers, a prompt's prefill chunks and their
    sub-chunks: host integers, for the counter."""
    sub_chunk = min(rule.chunk, chunk)
    nb = sub_chunk // kda_block(sub_chunk)
    n = layers * -(-prompt_tokens // chunk) * (chunk // sub_chunk)
    return n * nb, n * nb * (nb - 1) // 2


def recurrent_state_bytes(one: int, layers: int, prompt_tokens: int,
                          rows: int, new: int, chunk: int) -> dict[str, int]:
    """Bytes of fixed-size state (``one``: one row's in one layer: the
    delta rule's float32 state and conv tails, or a gated conv's tail)
    that ``layers`` such layers read and write in a job, by phase: each
    prefill chunk reads one row's once and writes it once, each of the
    decode's ``new - 1`` steps every row's."""
    return {"prefill": layers * -(-prompt_tokens // chunk) * 2 * one,
            "decode": layers * (new - 1) * rows * 2 * one}


# ---- the gated short convolution ------------------------------------------


@scope("recurrence")
def gated_conv(p, x, tail, n_valid):
    """x (B, T, d) -> (y, tail). ``[B | C | u] = x W_in`` (``in_proj``, d
    -> 3d, split in that order), ``z = B * u``, ``v`` = z through the
    depthwise causal conv ``conv`` (K, d) with no activation, ``y = (C *
    v) W_out``. ``tail`` (B, K-1, d) holds the last K-1 values of z
    before x (zeros at a sequence's start); the returned tail is the last
    K-1 after ``n_valid`` tokens. One function for a prefill chunk and a
    decode step (T = 1: the tail rolls by one)."""
    b, c, u = jnp.split(proj(x, p["in_proj"]), 3, axis=-1)
    z, tail = short_conv(b * u, tail, p["conv"], n_valid, activation=None)
    return proj(c * z, p["out_proj"]), tail


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
    (B, S, latent + Dr) holds every earlier token's. Attends causally
    over the written cache (``ops.attention.latent_prefill_attention``):
    the sweep up-projects a head's keys and values of each key block it
    reads from the latents and W_ukv itself, so that no (B, S, H x D)
    keys or values exist in memory, and blocks past the written length
    are neither read nor up-projected; the rotary key goes in as it lies
    in the cache, one for all heads."""
    t = x.shape[1]
    h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    q_n, q_r, entry = _query_and_entry(p, cfg, x, q, pos + jnp.arange(t),
                                       inv_freq, rope_amplitude)
    cache = jax.lax.dynamic_update_slice_in_dim(cache, entry, pos, axis=1)
    o = latent_prefill_attention(q_n, cache, p["wukv"].reshape(rank, h, -1),
                                 pos, shared_key=(q_r, cache[..., rank:]),
                                 scale=scale)
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


def prefill_up_projections(layers: int, heads: int, prompt_tokens: int,
                           chunk: int, capacity: int) -> dict[str, int]:
    """(head, key block) up-projections of ``layers`` latent-attention
    layers' prefill over a prompt's chunks, by where they are made:
    ``kernel`` = inside the causal sweep, one a step that holds work
    (``up_projected_blocks``); ``memory`` = keys and values written to
    memory ahead of the sweep, the form before the sweep took latents,
    which no layer takes."""
    return {"kernel": layers * heads * sum(
                up_projected_blocks(chunk, pos, capacity)
                for pos in range(0, prompt_tokens, chunk)),
            "memory": 0}


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
                         inv_freq, amplitude: float,
                         qk_norm_eps: float | None = None):
    """q (B, T, H, D) and k, v (B, T, Hk, D) of the normed input x
    (B, T, d), q and k each RMS-normed a head (gains ``q_norm`` /
    ``k_norm``, eps ``qk_norm_eps``; not where it is None) and then
    rotated at ``positions`` (T,); neither rotated where ``inv_freq`` is
    None (no position encoding)."""
    b_, t, _ = x.shape
    q = proj(x, p["wq"]).reshape(b_, t, heads, -1)
    k = proj(x, p["wk"]).reshape(b_, t, kv_heads, -1)
    v = proj(x, p["wv"]).reshape(b_, t, kv_heads, -1)
    if qk_norm_eps is not None:
        q = rms_norm(q, p["q_norm"], qk_norm_eps)
        k = rms_norm(k, p["k_norm"], qk_norm_eps)
    if inv_freq is None:
        return q, k, v
    return (_rotated(q, positions, inv_freq, amplitude),
            _rotated(k, positions, inv_freq, amplitude), v)


def _written(cache, k, v, at):
    return {"k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, at,
                                                     axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, at,
                                                     axis=1)}


def kv_prefill(p, x, cache, pos, n_valid, *, heads: int, inv_freq,
               scale: float, rope_amplitude: float = 1.0,
               qk_norm_eps: float | None = None,
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
                                   kv_heads, inv_freq, rope_amplitude,
                                   qk_norm_eps)
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
              qk_norm_eps: float | None = None,
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
                                   kv_heads, inv_freq, rope_amplitude,
                                   qk_norm_eps)
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


def kv_decode_key_blocks(layers: int, prompt_tokens: int, new: int,
                         capacity: int) -> tuple[int, int]:
    """(key blocks the decode's sweep reads over ``new - 1`` steps of
    ``layers`` FULL layers, key blocks of the whole capacity over the
    same steps)."""
    block = prompt_key_block(capacity)
    steps = layers * (new - 1)
    return (steps * -(-prompt_tokens // block),
            steps * -(-capacity // block))


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


@scope("experts")
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


def sigmoid_route(p, cfg, x):
    """x (T, d) -> (chosen experts (T, K) int32, their weights (T, K)
    float32): sigmoid scores over ALL experts; chosen by score + bias,
    the best ``topk_group`` of ``n_group`` groups by the sum of their top
    two (one group: no choice of groups), then the best K inside them;
    weights are the scores of the chosen over their sum (plus the
    configuration's ``norm_topk_eps`` where it has one), times the
    scaling factor."""
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
    total = jnp.sum(weight, -1, keepdims=True)
    eps = getattr(cfg, "norm_topk_eps", 0.0)
    if eps:
        total = total + eps
    weight = weight / total * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), weight


def moe(p, cfg, x, route, valid=None):
    """x (..., d) -> (shared expert, where the layer has one, + held
    experts' part, stats). ``route(p, cfg, x (T, d))`` is the stack's
    router: (chosen experts (T, K) int32, their weights (T, K) float32)
    over ALL experts."""
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    if valid is None:
        valid = jnp.ones((flat.shape[0],), bool)
    with scope("router"):
        chosen, weight = route(p, cfg, flat)
    y, held, hit = held_experts_part(p, cfg, flat, chosen, weight,
                                     valid.reshape(-1))
    if "shared" in p:
        with scope("shared_expert"):
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
        with scope("dense_mlp"):
            y = swiglu(layer["mlp"], h)
        return x + y, stats
    y, s = moe(layer["mlp"], cfg, h, route, valid)
    return x + y, {k: stats[k] + s[k].astype(jnp.int32) for k in stats}
