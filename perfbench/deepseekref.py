"""Plain reference of a DeepSeek-V2-class decoder: token ids in, logits
out. The yardstick of the ``textgen_deepseek`` kind
(``perfbench/kinds/textgen_deepseek.py``).

Straight ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``; no module of the program, no cache, no absorbed
form, no chunks, no kernel: every layer up-projects every key and value
and attends over all of them under a causal mask, and the experts are a
loop over the experts held. It reads its sizes from a plain dict of the
published ``config.json`` keys (``sizes`` below makes it from a
configuration file) and the weights by the names of the checkpoint
layout, which is all it shares with the program. What does not know the
model (the rounded product, the norm, the SwiGLU, the blocked causal
softmax, the per-expert program, the head) is ``perfbench/textref.py``'s.

The layer equations (``x`` a layer's input, no biases anywhere, RMSNorm
with a gain): ``h = x + MLA(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))``.

- MLA: ``c_q = RMSNorm(W_dq x)``; per head ``[q_n, q_r] = W_uq c_q``;
  ``[c_kv, k_r] = W_dkv x``; ``c = RMSNorm(c_kv)``; ``q_r``, ``k_r``
  rotated (``k_r`` one for all heads); per head ``[k_n, v] = W_ukv c``;
  ``score = (q_n . k_n + q_r . k_r) * s``, ``s = (nope + rope)^-0.5 *
  m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax;
  ``out = W_o [heads' sum of p v]``.
- RoPE, YaRN: ``f_i = theta^(-2i/d)`` over the d/2 pairs of the rope
  dimensions; ``inv_freq_i = (f_i / factor) (1 - g_i) + f_i g_i``, ``g_i
  = 1 - clip((i - low) / (high - low), 0, 1)``, ``low, high`` = floor /
  ceil of ``d ln(original / (beta 2 pi)) / (2 ln theta)`` at beta_fast
  and beta_slow, clamped to [0, d - 1]; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
- MLP: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``p = softmax(W_g x)``
  over all routed experts in float32; a group's score is the max of its
  experts'; the best ``topk_group`` groups stay, the rest are zeroed;
  the best ``num_experts_per_tok`` of what is left are chosen; weights
  are their ``p``, not normalised, times ``routed_scaling_factor``;
  ``MLP(x) = S(x) + sum over the chosen experts HELD of w_e E_e(x)``,
  ``S`` one SwiGLU of width ``n_shared_experts x moe_intermediate_size``.
  What the absent experts would add is left out.

Departures from the published implementation, each also under
``assumed`` in the configuration's file: the rope layout is rotate-half
over the 64 rope dimensions where the published code de-interleaves
pairs first (with seeded weights a relabelling of W_uq's and W_dkv's
columns); ``q_a_layernorm`` / ``kv_a_layernorm`` are plain RMSNorm with
a gain; positions count from 0 with no BOS token.

Attention runs over groups of ``HEAD_GROUP`` heads (``lax.map``), each
group's queries in blocks, so that 16,384 tokens at 128 heads fit beside
the weights: a group's keys and values are up-projected inside its turn
and never held for all heads at once. A job's rows share their prompt,
so one pass serves several rows (``forward_tree``, as in
``perfbench/textref.py``): a row's attention sees the prompt's keys and
its own.

``precision`` rounds the operands of every product of activations and
weights as ``perfbench/reference.py`` does (``fp8``: the control that
``correct`` has to fail); the router stays float32, as the configuration
states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import HIGHEST
from perfbench.textref import (  # noqa: F401
    _dense_block,
    _expert_add,
    _frozen,
    _head_block,
    _normed,
    _with_shared,
    causal_attend,
    mm,
    rms,
    token_logprobs,
)

#: the published keys the reference reads (others in a configuration's
#: file say nothing about these layers)
KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor", "rms_norm_eps")
YARN_KEYS = ("factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
             "original_max_position_embeddings")

#: heads whose keys and values are up-projected and held at once
HEAD_GROUP = 8


def sizes(config: dict) -> dict:
    """The reference's view of a configuration file: the published keys,
    the YaRN group as sorted pairs (hashable), ``router_outputs`` (the
    published expert count: the router is never cut) and
    ``experts_held`` [first, past the last]."""
    c = {key: config[key] for key in KEYS}
    c["rope_scaling"] = tuple(sorted(
        (key, config["rope_scaling"][key]) for key in YARN_KEYS))
    published = config.get("published", {})
    c["router_outputs"] = int(published.get("n_routed_experts",
                                            config["n_routed_experts"]))
    c["experts_held"] = list(config.get(
        "experts_held", [0, config["n_routed_experts"]]))
    return c


# ---- YaRN ------------------------------------------------------------------


def mscale_of(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(c: dict):
    """(frequencies (d/2,) float32, the factor on cos and sin, the
    softmax scale)."""
    y = dict(c["rope_scaling"])
    dim, theta = c["qk_rope_head_dim"], c["rope_theta"]

    def pair_with(turns):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_with(y["beta_fast"])), 0)
    high = min(math.ceil(pair_with(y["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    g = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    m = mscale_of(y["factor"], y["mscale_all_dim"])
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return ((f / y["factor"] * (1.0 - g) + f * g).astype(np.float32),
            mscale_of(y["factor"], y["mscale"]) / m, width ** -0.5 * m * m)


def rope(x, positions, freq, amplitude):
    """Rotate-half over the last axis; x (T, ..., D), positions (T,)."""
    half = x.shape[-1] // 2
    angle = jnp.asarray(positions, jnp.float32)[:, None] * freq[None]
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2)
                          + angle.shape[1:])
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---- MLA -------------------------------------------------------------------


def mla_layer(p, c, x_p, x_rows, precision):
    """Attention of the prompt (P, d) and of each row (N, d) after it:
    (y_p, [y_row, ...])."""
    h, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, dv = c["qk_nope_head_dim"], c["v_head_dim"]
    freq, amplitude, scale = yarn(c)
    group = min(HEAD_GROUP, h)
    n = x_p.shape[0]

    def per_token(x, positions):
        """(c_q (T, q_rank), c (T, rank), rotated k_r (T, Dr))."""
        c_q = rms(mm(x, p["wdq"], precision), p["q_norm"], c["rms_norm_eps"])
        ckr = mm(x, p["wdkv"], precision)
        latent = rms(ckr[:, :rank], p["kv_norm"], c["rms_norm_eps"])
        return c_q, latent, rope(ckr[:, rank:], positions, freq, amplitude)

    def heads_of(w_uq, w_ukv, tokens, positions):
        """One group's q, k (T, G, nope + rope) and v (T, G, Dv)."""
        c_q, latent, k_r = tokens
        t = c_q.shape[0]
        q = mm(c_q, w_uq, precision).reshape(t, group, -1)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions,
                                                 freq, amplitude)], -1)
        kv = mm(latent, w_ukv, precision).reshape(t, group, -1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_r[:, None], (t, group, k_r.shape[-1]))], -1)
        return q, k, kv[..., nope:]

    positions = [np.arange(n)] + [n + np.arange(x.shape[0]) for x in x_rows]
    tokens = [per_token(x, pos)
              for x, pos in zip([x_p] + list(x_rows), positions)]

    def one_group(weights):
        w_uq, w_ukv = weights
        q, k, v = heads_of(w_uq, w_ukv, tokens[0], positions[0])
        outs = [causal_attend(q, k, v, 0, scale, precision)]
        for own, pos in zip(tokens[1:], positions[1:]):
            q_r, k_r, v_r = heads_of(w_uq, w_ukv, own, pos)
            outs.append(causal_attend(
                q_r, jnp.concatenate([k, k_r]), jnp.concatenate([v, v_r]),
                n, scale, precision))
        return outs

    def grouped(w, per_head):
        """(in, H * per_head) -> (H / G, in, G * per_head)."""
        return jnp.moveaxis(
            w.reshape(w.shape[0], h // group, group * per_head), 1, 0)

    outs = jax.lax.map(one_group, (
        grouped(p["wuq"], nope + c["qk_rope_head_dim"]),
        grouped(p["wukv"], nope + dv)))
    # (H / G, T, G, Dv) -> (T, H * Dv)
    return [mm(jnp.moveaxis(o, 0, 1).reshape(o.shape[1], -1), p["wo"],
               precision) for o in outs]


# ---- experts ---------------------------------------------------------------


def route(p, c, x):
    """(chosen (T, K), weights (T, K)) over ALL experts, float32."""
    t = x.shape[0]
    scores = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                                    p["router"].astype(jnp.float32),
                                    precision=HIGHEST), -1)
    groups = scores.reshape(t, c["n_group"], -1)
    best = jnp.argsort(-jnp.max(groups, -1), -1,
                       stable=True)[:, :c["topk_group"]]
    keep = jnp.zeros((t, c["n_group"]), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    masked = jnp.where(keep[..., None], groups, 0.0).reshape(t, -1)
    chosen = jnp.argsort(-masked, -1, stable=True)[
        :, :c["num_experts_per_tok"]]
    weight = jnp.take_along_axis(scores, chosen, -1) \
        * c["routed_scaling_factor"]
    return chosen, weight


@functools.partial(jax.jit, static_argnames=("ckey",))
def _route(router, x, ckey):
    return route({"router": router}, dict(ckey), x)


def moe_layer(p, c, x, precision, held=None, shared=True, pad=128):
    """x (T, d) -> shared experts + the held experts' weighted outputs.
    ``p["experts"]`` holds the experts ``held`` = [first, past the last)
    in that order. A loop over the experts: each takes the tokens routed
    to it (their count padded up to a multiple of ``pad`` with a dummy
    row of weight 0, so that the products come in few shapes)."""
    first, past = c["experts_held"] if held is None else held
    t = x.shape[0]
    chosen, weight = _route(p["router"], x, _frozen(c))
    chosen, weight = np.asarray(chosen), np.asarray(weight, np.float32)
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    y = jnp.zeros((t + 1, x.shape[1]), jnp.float32)
    for e in range(first, past):
        rows, slots = np.nonzero(chosen == e)
        if rows.size == 0:
            continue
        fill = -rows.size % pad
        index = np.concatenate([rows, np.full(fill, t)])
        w = np.concatenate([weight[rows, slots], np.zeros(fill, np.float32)])
        y = _expert_add(y, x_pad, p["experts"], np.int32(e - first), index,
                        w, precision)
    return _with_shared(y[:t], p["shared"], x, precision) if shared \
        else y[:t]


# ---- the stack -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ckey", "precision"))
def _attn_block(norm, p, x_p, x_rows, ckey, precision):
    """x + MLA(rmsnorm(x)) over the prompt and each row: one program (a
    layer's many small operations dispatched one by one take minutes on
    the chip)."""
    c = dict(ckey)
    eps = c["rms_norm_eps"]
    y_p, *y_rows = mla_layer(
        p, c, rms(x_p, norm, eps), [rms(x, norm, eps) for x in x_rows],
        precision)
    return x_p + y_p, [x + y for x, y in zip(x_rows, y_rows)]


def hidden_tree(params, c: dict, prompt, rows, precision):
    """Final hidden states of the prompt (P, d) and of each row (N, d)."""
    prompt, rows = np.asarray(prompt), np.asarray(rows)
    n_p, ckey = len(prompt), _frozen(c)
    embed = params["embed"]
    x_p = embed[prompt].astype(jnp.float32)
    x_rows = [embed[row].astype(jnp.float32) for row in rows]
    for i, layer in enumerate(params["layers"]):
        x_p, x_rows = _attn_block(layer["attn_norm"], layer["attn"], x_p,
                                  x_rows, ckey, precision)
        x_all = jnp.concatenate([x_p] + x_rows)
        if i >= c["first_k_dense_replace"]:
            h = _normed(layer["mlp_norm"], x_all, ckey)
            x_all = x_all + moe_layer(layer["mlp"], c, h, precision)
        else:
            x_all = _dense_block(layer["mlp_norm"], layer["mlp"], x_all,
                                 ckey, precision)
        x_p, at = x_all[:n_p], n_p
        for r, x in enumerate(x_rows):
            x_rows[r] = x_all[at:at + x.shape[0]]
            at += x.shape[0]
    return x_p, x_rows


def _head(params, c, x, precision):
    return _head_block(params["final_norm"], params["head"], x,
                       c["rms_norm_eps"], precision)


def forward_tree(params, c: dict, prompt, rows, precision="float32"):
    """Logits (len(rows), N, V) at the positions each row's tokens are
    predicted from: the prompt's last token, then the row's own tokens
    but its last. ``prompt`` (P,) ids, ``rows`` (n, N) ids."""
    x_p, x_rows = hidden_tree(params, c, prompt, rows, precision)
    return jnp.stack([_head(params, c, jnp.concatenate([x_p[-1:], x[:-1]]),
                            precision) for x in x_rows])


def forward(params, c: dict, ids, precision="float32"):
    """One sequence, whole: logits (T, V) after every token."""
    x, _ = hidden_tree(params, c, ids, [], precision)
    return _head(params, c, x, precision)
