"""Plain reference of an LFM2-MoE-class decoder: token ids in, logits out.
The yardstick of the ``textgen_lfm2`` kind
(``perfbench/kinds/textgen_lfm2.py``).

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision(
"highest")`` (and every product at ``Precision.HIGHEST``); no module of
the program, no cache, no chunks, no kernel: a conv layer convolves the
whole sequence at once, an attention layer computes every key and value
and attends over all of them under a dense causal mask
(``lagunaref.attend``: a key-value head at a time, the queries in
blocks, so that 16,384 + 128 positions fit), and the experts are a loop
over the experts held. It reads its sizes from a plain dict of the
published ``config.json`` keys (``sizes`` below makes it from a
configuration file) and the weights by the names of the checkpoint
layout, which is all it shares with the program.

The layer equations (``x`` a layer's input, no biases anywhere, RMSNorm
with a gain, eps ``norm_eps``): ``h = x + M(RMSNorm(x))``, ``y = h +
MLP(RMSNorm(h))``; after the last layer RMSNorm and the head, which is
the embedding (logits ``h E^T``).

- ``layer_types[i]`` ``conv``: ``[B | C | u] = x W_in`` (d -> 3d, split
  in that order), ``z = B * u``, ``v_t = sum_i w_i * z_(t-K+1+i)`` over
  the ``conv_L_cache`` = K taps a channel (z before the first token is
  0; no activation), ``y = (C * v) W_out``.
- ``full_attention``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` of ``head_dim`` = hidden / heads: q = W_q x,
  k = W_k x, v = W_v x; q and k RMS-normed a head over its ``head_dim``
  values (gains ``q_norm``, ``k_norm``), then rotate-half RoPE over all
  of them at ``rope_theta``; query head j reads key-value head j // (H /
  Hk); softmax(q k / sqrt(head_dim)) causal; y = W_o o, no gate.
- MLP: layers below ``num_dense_layers`` a SwiGLU of
  ``intermediate_size``; every later one ``s = sigmoid(W_r x)`` over all
  ``num_experts`` in float32, the ``num_experts_per_tok`` largest of s +
  bias chosen (ties to the lower index), weights the chosen s / (their
  sum + 1e-6) (``norm_topk_prob``) times ``routed_scaling_factor``;
  ``MLP(x) = sum over the chosen experts HELD of w_e E_e(x)``, every
  expert a SwiGLU of ``moe_intermediate_size``, no shared expert. What
  the absent experts would add is left out.

Departures from the published modelling code, each an ``assumed``
reading of the configuration's file: every value in float32 where the
published code computes in the checkpoint's dtype; the head tied to the
embedding (the catalog's config has no tie key; the family's total of
8.3 B parameters holds only so); the norms' gains multiply (no ``1 +``
offset).

A job's rows share their prompt, so one pass serves several rows
(``forward_tree``, as in ``perfbench/textref.py``): a row's convs start
from the prompt's last K - 1 values of z, its attention sees the
prompt's keys and its own.

``precision`` rounds the operands of every product of activations and
weights as ``perfbench/reference.py`` does (``fp8``: the control that
``correct`` has to fail); the router stays float32, as the configuration
states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lagunaref import attend, rope
from perfbench.reference import HIGHEST
from perfbench.textref import (  # noqa: F401
    _dense_block,
    _expert_add,
    _frozen,
    _normed,
    mm,
    rms,
    token_logprobs,
)

#: the published keys the reference reads (others in a configuration's
#: file say nothing about these layers)
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "layer_types", "num_dense_layers",
        "num_experts_per_tok", "routed_scaling_factor", "norm_eps",
        "rope_theta", "conv_L_cache")
CONV, ATTENTION = "conv", "full_attention"

#: ``norm_topk_prob``'s guard on the chosen scores' sum
TOPK_EPS = 1e-6


def sizes(config: dict) -> dict:
    """The reference's view of a configuration file: the published keys
    (``norm_eps`` also as ``rms_norm_eps``, the name of the helpers it
    borrows), ``router_outputs`` (the published expert count: the router
    is never cut) and ``experts_held`` [first, past the last]."""
    c = {key: config[key] for key in KEYS}
    c["layer_types"] = tuple(c["layer_types"])
    c["rms_norm_eps"] = c["norm_eps"]
    published = config.get("published", {})
    c["router_outputs"] = int(published.get("num_experts",
                                            config["num_experts"]))
    c["experts_held"] = list(config.get("experts_held",
                                        [0, config["num_experts"]]))
    return c


# ---- the gated short convolution -------------------------------------------


def conv_layer(p, c, x_p, x_rows, precision):
    """The conv of the prompt (P, d) and of each row (N, d) after it,
    whose taps reach back into the prompt: (y_p, [y_row, ...])."""
    k = c["conv_L_cache"]
    w = p["conv"].astype(jnp.float32)

    def one(x, history):
        t = x.shape[0]
        b, gate, u = jnp.split(mm(x, p["in_proj"], precision), 3, -1)
        seq = jnp.concatenate([history, b * u])
        v = sum(seq[i:i + t] * w[i] for i in range(k))
        return mm(gate * v, p["out_proj"], precision), seq[-(k - 1):]

    y_p, history = one(x_p, jnp.zeros((k - 1, x_p.shape[1]), jnp.float32))
    return y_p, [one(x, history)[0] for x in x_rows]


# ---- softmax attention -----------------------------------------------------


def attention_layer(p, c, x_p, x_rows, precision):
    """Attention of the prompt (P, d) and of each row (N, d) after it:
    (y_p, [y_row, ...])."""
    h, hk, d = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    freq = (c["rope_theta"] ** (-2.0 * np.arange(d // 2) / d)
            ).astype(np.float32)
    n = x_p.shape[0]

    def heads_of(x, positions):
        """q (Hk, T, G, D), k and v (Hk, T, D), by key-value head."""
        t = x.shape[0]
        q = rms(mm(x, p["wq"], precision).reshape(t, h, d), p["q_norm"],
                c["norm_eps"])
        k = rms(mm(x, p["wk"], precision).reshape(t, hk, d), p["k_norm"],
                c["norm_eps"])
        v = mm(x, p["wv"], precision).reshape(t, hk, d)
        q, k = rope(q, positions, freq, 1.0), rope(k, positions, freq, 1.0)
        return (jnp.moveaxis(q.reshape(t, hk, h // hk, d), 1, 0),
                jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0))

    positions = [np.arange(n)] + [n + np.arange(x.shape[0]) for x in x_rows]
    tokens = [heads_of(x, pos)
              for x, pos in zip([x_p] + list(x_rows), positions)]

    def one_head(parts):
        (q, k, v), *rows = parts
        outs = [attend(q, k, v, 0, None, d ** -0.5, precision)]
        for q_r, k_r, v_r in rows:
            outs.append(attend(q_r, jnp.concatenate([k, k_r]),
                               jnp.concatenate([v, v_r]), n, None,
                               d ** -0.5, precision))
        return outs

    outs = jax.lax.map(one_head, tokens)       # each (Hk, T, G, D)
    ys = [mm(jnp.moveaxis(o, 0, 1).reshape(x.shape[0], -1), p["wo"],
             precision) for x, o in zip([x_p] + list(x_rows), outs)]
    return ys[0], ys[1:]


# ---- experts ---------------------------------------------------------------


def route(p, c, x):
    """(chosen (T, K), weights (T, K)) over ALL experts, float32."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    p["router"].astype(jnp.float32),
                                    precision=HIGHEST))
    chosen = jnp.argsort(-(scores + p["router_bias"]), -1, stable=True)[
        :, :c["num_experts_per_tok"]]
    weight = jnp.take_along_axis(scores, chosen, -1)
    weight = weight / (weight.sum(-1, keepdims=True) + TOPK_EPS) \
        * c["routed_scaling_factor"]
    return chosen, weight


@functools.partial(jax.jit, static_argnames=("ckey",))
def _route(router, router_bias, x, ckey):
    return route({"router": router, "router_bias": router_bias},
                 dict(ckey), x)


def moe_layer(p, c, x, precision, held=None, pad=128):
    """x (T, d) -> the held experts' weighted outputs. ``p["experts"]``
    holds the experts ``held`` = [first, past the last) in that order. A
    loop over the experts: each takes the tokens routed to it (their
    count padded up to a multiple of ``pad`` with a dummy row of weight
    0, so that the products come in few shapes)."""
    first, past = c["experts_held"] if held is None else held
    t = x.shape[0]
    chosen, weight = _route(p["router"], p["router_bias"], x, _frozen(c))
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
    return y[:t]


# ---- the stack -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ckey", "kind", "precision"))
def _mixer_block(norm, p, x_p, x_rows, ckey, kind, precision):
    """x + mixer(rmsnorm(x)) over the prompt and each row: one program a
    layer kind (a layer's many small operations dispatched one by one
    take minutes on the chip)."""
    c = dict(ckey)
    eps = c["norm_eps"]
    y_p, y_rows = (attention_layer if kind == ATTENTION else conv_layer)(
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
        x_p, x_rows = _mixer_block(layer["attn_norm"], layer["attn"], x_p,
                                   x_rows, ckey, c["layer_types"][i],
                                   precision)
        x_all = jnp.concatenate([x_p] + x_rows)
        if i >= c["num_dense_layers"]:
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


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _tied_head(norm, embed, x, eps, precision):
    return mm(rms(x, norm, eps), embed.T, precision)


def _head(params, c, x, precision):
    return _tied_head(params["final_norm"], params["embed"], x,
                      c["norm_eps"], precision)


def forward_tree(params, c: dict, prompt, rows, precision="float32"):
    """Logits (len(rows), N, V) at the positions each row's tokens are
    predicted from: the prompt's last token, then the row's own tokens
    but its last. ``prompt`` (P,) ids, ``rows`` (n, N) ids."""
    with jax.default_matmul_precision("highest"):
        x_p, x_rows = hidden_tree(params, c, prompt, rows, precision)
        return jnp.stack([_head(params, c,
                                jnp.concatenate([x_p[-1:], x[:-1]]),
                                precision) for x in x_rows])


def forward(params, c: dict, ids, precision="float32"):
    """One sequence, whole: logits (T, V) after every token."""
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_tree(params, c, ids, [], precision)
        return _head(params, c, x, precision)
