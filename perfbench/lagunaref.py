"""Plain reference of a Laguna-XS.2-class decoder: token ids in, logits
out. The yardstick of the ``textgen_laguna`` kind
(``perfbench/kinds/textgen_laguna.py``).

Straight ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``; no module of the program, no cache, no chunks, no
kernel, no window buffer: every layer computes every key and value and
attends over all of them under a dense mask (causal; in a sliding layer
also the window's), and the experts are a loop over the experts held. It
reads its sizes from a plain dict of the published ``config.json`` keys
(``sizes`` below makes it from a configuration file) and the weights by
the names of the checkpoint layout, which is all it shares with the
program. What does not know the model (the rounded product, the norm,
the SwiGLU, the per-expert program, the head) is
``perfbench/textref.py``'s.

The layer equations (``x`` a layer's input, no biases anywhere, RMSNorm
with a gain, eps ``rms_norm_eps``): ``h = x + A(RMSNorm(x))``, ``y = h +
MLP(RMSNorm(h))``; after the last layer RMSNorm and the untied head.

- Attention of layer i, type ``layer_types[i]``, ``H =
  num_attention_heads_per_layer[i]`` query heads over ``Hk =
  num_key_value_heads`` key-value heads of ``D = head_dim``: ``q = W_q
  x`` (H x D), ``k = W_k x``, ``v = W_v x`` (Hk x D); no norm on q or
  k. Rotary, rotate-half over the first ``partial_rotary_factor x D``
  values of every head of q and k, the rest untouched: a
  ``full_attention`` layer with YaRN (``f_i = theta^(-2i/d)`` over the
  d/2 pairs of the d rotated values; ``inv_freq_i = (f_i / factor) (1 -
  g_i) + f_i g_i``, ``g_i = 1 - clip((i - low) / (high - low), 0, 1)``,
  ``low, high`` = floor / ceil of ``d ln(original / (beta 2 pi)) / (2 ln
  theta)`` at ``beta_fast`` and ``beta_slow``, clamped to [0, d - 1]; cos
  and sin times ``attention_factor``), a ``sliding_attention`` layer
  with the plain ``f_i``, amplitude 1. ``score = q . k * D^-0.5``; query
  head j reads key-value head ``j // (H / Hk)``; key c is visible to
  position p when ``c <= p`` and, in a sliding layer, ``c > p -
  sliding_window``; softmax; each head's read-out times ``sigmoid(W_g
  x)_j`` (one gate value a query head); then ``W_o``.
- MLP: ``mlp_layer_types[i]`` ``dense``: SwiGLU of ``intermediate_size``.
  ``sparse``: ``p = softmax(W_r x)`` over all experts in float32; the
  ``num_experts_per_tok`` largest are chosen (ties to the lower index);
  weights ``p_e / sum of the chosen p`` times
  ``moe_routed_scaling_factor``; ``MLP(x) = S(x) + sum over the chosen
  experts HELD of w_e E_e(x)``, ``S`` one SwiGLU of
  ``shared_expert_intermediate_size``, every expert one of
  ``moe_intermediate_size``. What the absent experts would add is left
  out.

Every reading the published config leaves open is under ``assumed`` in
the configuration's file.

Attention runs one key-value head at a time (``lax.map`` over the heads,
each with its ``H / Hk`` query heads), the queries in blocks, so that
16,384 + 128 positions at 64 heads fit beside the weights. A job's rows
share their prompt, so one pass serves several rows (``forward_tree``,
as in ``perfbench/textref.py``): a row's attention sees the prompt's
keys and its own.

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

from perfbench.reference import HIGHEST, _round
from perfbench.textref import (  # noqa: F401
    _dense_block,
    _expert_add,
    _frozen,
    _head_block,
    _normed,
    _with_shared,
    mm,
    rms,
    token_logprobs,
)

#: the published keys the reference reads (others in a configuration's
#: file say nothing about these layers)
KEYS = ("hidden_size", "num_hidden_layers", "num_key_value_heads",
        "head_dim", "layer_types", "num_attention_heads_per_layer",
        "mlp_layer_types", "sliding_window", "num_experts_per_tok",
        "moe_routed_scaling_factor", "rms_norm_eps")
LAYER_TYPES = ("full_attention", "sliding_attention")
ROPE_KEYS = ("rope_theta", "rope_type", "partial_rotary_factor")
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")


def sizes(config: dict) -> dict:
    """The reference's view of a configuration file: the published keys,
    each layer type's rotary group as sorted pairs (hashable),
    ``router_outputs`` (the expert count: the router is never cut) and
    ``experts_held`` [first, past the last]."""
    c = {key: config[key] for key in KEYS}
    for kind in LAYER_TYPES:
        group = config["rope_parameters"][kind]
        keys = ROPE_KEYS + (YARN_KEYS if group["rope_type"] == "yarn" else ())
        c[kind] = tuple(sorted((key, group[key]) for key in keys))
    published = config.get("published", {})
    c["router_outputs"] = int(published.get("num_experts",
                                            config["num_experts"]))
    c["experts_held"] = list(config.get("experts_held",
                                        [0, config["num_experts"]]))
    return c


# ---- rotary ----------------------------------------------------------------


def frequencies(c: dict, kind: str):
    """(frequencies of the rotated values (as many as half of them)
    float32, the factor on cos and sin) of a layer type."""
    r = dict(c[kind])
    dim, theta = int(c["head_dim"] * r["partial_rotary_factor"]), \
        r["rope_theta"]
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    if r["rope_type"] != "yarn":
        return f.astype(np.float32), 1.0

    def pair_with(turns):
        return dim * math.log(r["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_with(r["beta_fast"])), 0)
    high = min(math.ceil(pair_with(r["beta_slow"])), dim - 1)
    g = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((f / r["factor"] * (1.0 - g) + f * g).astype(np.float32),
            float(r["attention_factor"]))


def rope(x, positions, freq, amplitude):
    """Rotate-half over the first ``2 x len(freq)`` values of the last
    axis, the rest as they are; x (T, H, D), positions (T,)."""
    width = 2 * len(freq)
    angle = (jnp.asarray(positions, jnp.float32)[:, None]
             * freq[None])[:, None, :]
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    a, b = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., width:]], -1)


# ---- attention -------------------------------------------------------------


def attend(q, k, v, offset, window, scale, precision, block=256):
    """One key-value head: q (L, G, D) at positions ``offset + l`` over
    k, v (S, D) under a dense mask (causal, and the last ``window`` keys
    where there is a window). In blocks of queries (``lax.map``), so that
    the logits of a 16k prompt fit; the last block is padded with zero
    queries, whose rows are dropped."""
    n = q.shape[0]
    block = min(block, n)
    pad = -n % block
    q = _round(jnp.pad(q, ((0, pad), (0, 0), (0, 0))), precision)
    k, v = _round(k, precision), _round(v, precision)
    key_pos = jnp.arange(k.shape[0])

    def one(args):
        qb, start = args
        logits = jnp.einsum("lgd,sd->gls", qb, k, precision=HIGHEST) * scale
        q_pos = offset + start + jnp.arange(block)
        visible = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            visible &= key_pos[None, :] > q_pos[:, None] - window
        weights = jax.nn.softmax(
            jnp.where(visible[None], logits, -jnp.inf), -1)
        return jnp.einsum("gls,sd->lgd", _round(weights, precision), v,
                          precision=HIGHEST)

    out = jax.lax.map(one, (q.reshape(-1, block, *q.shape[1:]),
                            jnp.arange(0, n + pad, block)))
    return out.reshape(-1, *out.shape[2:])[:n]


def attention_layer(p, c, layer, x_p, x_rows, precision):
    """Attention of the prompt (P, d) and of each row (N, d) after it:
    [y_p, y_row, ...]."""
    kind = c["layer_types"][layer]
    h, hk, d = (c["num_attention_heads_per_layer"][layer],
                c["num_key_value_heads"], c["head_dim"])
    window = c["sliding_window"] if kind == "sliding_attention" else None
    freq, amplitude = frequencies(c, kind)
    scale = d ** -0.5
    n = x_p.shape[0]

    def heads_of(x, positions):
        """q (Hk, T, G, D), k and v (Hk, T, D), by key-value head."""
        t = x.shape[0]
        q = rope(mm(x, p["wq"], precision).reshape(t, h, d), positions,
                 freq, amplitude)
        k = rope(mm(x, p["wk"], precision).reshape(t, hk, d), positions,
                 freq, amplitude)
        v = mm(x, p["wv"], precision).reshape(t, hk, d)
        return (jnp.moveaxis(q.reshape(t, hk, h // hk, d), 1, 0),
                jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0))

    positions = [np.arange(n)] + [n + np.arange(x.shape[0]) for x in x_rows]
    tokens = [heads_of(x, pos)
              for x, pos in zip([x_p] + list(x_rows), positions)]

    def one_head(parts):
        (q, k, v), *rows = parts
        outs = [attend(q, k, v, 0, window, scale, precision)]
        for q_r, k_r, v_r in rows:
            outs.append(attend(q_r, jnp.concatenate([k, k_r]),
                               jnp.concatenate([v, v_r]), n, window, scale,
                               precision))
        return outs

    outs = jax.lax.map(one_head, tokens)       # each (Hk, T, G, D)
    ys = []
    for x, o in zip([x_p] + list(x_rows), outs):
        t = x.shape[0]
        gate = jax.nn.sigmoid(mm(x, p["wg"], precision))       # (T, H)
        o = jnp.moveaxis(o, 0, 1).reshape(t, h, d) * gate[..., None]
        ys.append(mm(o.reshape(t, -1), p["wo"], precision))
    return ys


# ---- experts ---------------------------------------------------------------


def route(p, c, x):
    """(chosen (T, K), weights (T, K)) over ALL experts, float32."""
    probs = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                                   p["router"].astype(jnp.float32),
                                   precision=HIGHEST), -1)
    chosen = jnp.argsort(-probs, -1, stable=True)[
        :, :c["num_experts_per_tok"]]
    weight = jnp.take_along_axis(probs, chosen, -1)
    weight = weight / weight.sum(-1, keepdims=True) \
        * c["moe_routed_scaling_factor"]
    return chosen, weight


@functools.partial(jax.jit, static_argnames=("ckey",))
def _route(router, x, ckey):
    return route({"router": router}, dict(ckey), x)


def moe_layer(p, c, x, precision, held=None, shared=True, pad=128):
    """x (T, d) -> shared expert + the held experts' weighted outputs.
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


@functools.partial(jax.jit, static_argnames=("ckey", "layer", "precision"))
def _attn_block(norm, p, x_p, x_rows, ckey, layer, precision):
    """x + attention(rmsnorm(x)) over the prompt and each row: one
    program a layer (a layer's many small operations dispatched one by
    one take minutes on the chip)."""
    c = dict(ckey)
    eps = c["rms_norm_eps"]
    y_p, *y_rows = attention_layer(
        p, c, layer, rms(x_p, norm, eps), [rms(x, norm, eps) for x in x_rows],
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
                                  x_rows, ckey, i, precision)
        x_all = jnp.concatenate([x_p] + x_rows)
        if c["mlp_layer_types"][i] == "sparse":
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
