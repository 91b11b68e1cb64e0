"""Plain reference of a Solar-Open2-class decoder: token ids in, logits
out. The yardstick of the ``textgen_solar`` kind
(``perfbench/kinds/textgen_solar.py``).

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision(
"highest")`` (and every product at ``Precision.HIGHEST``); no module of
the program, no cache, no chunks, no kernel: the delta rule runs token by
token (``textref.kda_scan``, with this model's operands), a softmax
layer computes every key and value and attends over all of them under a
dense causal mask (``lagunaref.attend``: a key-value head at a time, the
queries in blocks, so that 16,384 + 64 positions at 64 heads fit), and
the experts are a loop over the experts held (``textref.moe_layer`` at
one router group). It reads its sizes from a plain dict of the published
``config.json`` keys (``sizes`` below makes it from a configuration
file) and the weights by the names of the checkpoint layout, which is
all it shares with the program.

The layer equations (``x`` a layer's input, no biases, RMSNorm with a
gain, eps ``rms_norm_eps``): ``h = x + A(RMSNorm(x))``, ``y = h +
MLP(RMSNorm(h))``; after the last layer RMSNorm and the untied head.

- Delta-rule layer i (i not in ``gqa_layers``), ``linear_attn_config``'s
  H heads of D (``num_kv_heads`` null: H key-value heads too): q~, k~,
  v~ = W x, each through a depthwise causal conv over time (kernel
  ``short_conv_kernel_size``) and SiLU; q = l2(q~) / sqrt(D), k = l2(k~)
  (l2(z) = z / sqrt(sum z^2 + 1e-6)); per head and channel ``log a_t =
  -exp(A_log_h) softplus(W_a_up W_a_down x + dt_bias)``; per head ``b_t
  = 2 sigmoid(W_b x)`` (``kda_allow_neg_eigval``); ``S_t = (I - b_t k_t
  k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t``; y =
  W_o [rmsnorm_head(o_t) * sigmoid(W_g_up W_g_down x)].
- Softmax layer i (i in ``gqa_layers``), ``num_attention_heads`` query
  heads over ``num_key_value_heads`` of ``head_dim``: q = W_q x, k = W_k
  x, v = W_v x, no rotary (``use_rope`` false) and no norm on q or k;
  query head j reads key-value head j // (H / Hk); softmax(q k / sqrt(D))
  causal; y = W_o [o * sigmoid(W_gate x)], the gate elementwise over the
  H x D read-out (``use_gqa_gate``).
- MLP: every layer from ``first_k_dense_replace`` on (all of them):
  ``s = sigmoid(W_r x)`` over all ``n_routed_experts`` in float32; the
  ``num_experts_per_tok`` largest of s + bias chosen (no groups); weights
  the chosen s over their sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``MLP(x) = S(x) + sum over the chosen
  experts HELD of w_e E_e(x)``, S one SwiGLU of ``moe_intermediate_size``
  (``n_shared_experts`` 1), every expert one of the same width. What the
  absent experts would add is left out.

Departures from the published description, each an ``assumed`` reading
of the configuration's file: the gates' low-rank pairs have rank D (128;
``kda_use_full_proj`` false says only that they are not full); the
softmax layers' gate is elementwise; the router's scoring is sigmoid
with a correction bias (the config names DeepSeek-V3's keys and no
scoring function); the shared expert has the routed experts' width.

A job's rows share their prompt, so one pass serves several rows
(``forward_tree``, as in ``perfbench/textref.py``): a row's recurrence
starts from the state and conv history after the prompt, and its
attention sees the prompt's keys and its own.

``precision`` rounds the operands of every product of activations and
weights as ``perfbench/reference.py`` does (``fp8``: the control that
``correct`` has to fail); the router and the recurrent state stay
float32, as the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lagunaref import attend
from perfbench.reference import silu
from perfbench.textref import (  # noqa: F401
    _dense_block,
    _frozen,
    _head_block,
    _normed,
    kda_scan,
    mm,
    moe_layer,
    rms,
    token_logprobs,
)

#: the published keys the reference reads (others in a configuration's
#: file say nothing about these layers)
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "gqa_layers",
        "first_k_dense_replace", "num_experts_per_tok",
        "routed_scaling_factor", "rms_norm_eps")
LINEAR_KEYS = ("short_conv_kernel_size", "head_dim", "num_heads")


def sizes(config: dict) -> dict:
    """The reference's view of a configuration file: the published keys,
    the ``linear_attn_config`` group's under ``linear_``, ``n_group`` =
    ``topk_group`` = 1 (``textref.route`` with no groups),
    ``router_outputs`` (the published expert count: the router is never
    cut) and ``experts_held`` [first, past the last]."""
    c = {key: config[key] for key in KEYS}
    c["gqa_layers"] = tuple(c["gqa_layers"])
    for key in LINEAR_KEYS:
        c[f"linear_{key}"] = config["linear_attn_config"][key]
    c["n_group"] = c["topk_group"] = 1
    published = config.get("published", {})
    c["router_outputs"] = int(published.get("n_routed_experts",
                                            config["n_routed_experts"]))
    c["experts_held"] = list(config.get("experts_held",
                                        [0, config["n_routed_experts"]]))
    return c


# ---- the delta rule --------------------------------------------------------


def kda_operands(p, c, x, history, precision):
    """x (T, d); ``history`` {q, k, v: (K-1, H*D)} the pre-conv values of
    the tokens before (zeros at the start of a sequence). Returns the
    recurrence's operands and this sequence's own pre-conv values."""
    t = x.shape[0]
    h, d = c["linear_num_heads"], c["linear_head_dim"]
    pre, out = {}, {}
    for name in "qkv":
        pre[name] = mm(x, p[f"w{name}"], precision)
        seq = jnp.concatenate([history[name], pre[name]])
        w = p[f"conv_{name}"].astype(jnp.float32)
        out[name] = silu(sum(seq[i:i + t] * w[i] for i in range(w.shape[0]))
                         ).reshape(t, h, d)

    def l2(z):
        return z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

    gate = mm(mm(x, p["wa_down"], precision), p["wa_up"], precision) \
        + p["dt_bias"]
    decay = -jnp.exp(p["a_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(gate.reshape(t, h, d))
    return {"q": l2(out["q"]) / d ** 0.5, "k": l2(out["k"]), "v": out["v"],
            "g": decay,
            "b": 2.0 * jax.nn.sigmoid(mm(x, p["wb"], precision))}, pre


def kda_out(p, c, x, o, precision):
    gate = mm(mm(x, p["wg_down"], precision), p["wg_up"], precision)
    o = rms(o, p["o_norm"], c["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate).reshape(o.shape)
    return mm(o.reshape(o.shape[0], -1), p["wo"], precision)


def kda_layer(p, c, x_p, x_rows, precision):
    h, d = c["linear_num_heads"], c["linear_head_dim"]
    k = c["linear_short_conv_kernel_size"] - 1
    zeros = {n: jnp.zeros((k, h * d), jnp.float32) for n in "qkv"}
    ops, pre = kda_operands(p, c, x_p, zeros, precision)
    o, state = kda_scan(ops, jnp.zeros((h, d, d), jnp.float32))
    y_p = kda_out(p, c, x_p, o, precision)
    history = {n: jnp.concatenate([zeros[n], pre[n]])[-k:] for n in "qkv"}
    y_rows = []
    for x in x_rows:
        ops, _ = kda_operands(p, c, x, history, precision)
        o, _ = kda_scan(ops, state)
        y_rows.append(kda_out(p, c, x, o, precision))
    return y_p, y_rows


# ---- softmax attention -----------------------------------------------------


def softmax_layer(p, c, x_p, x_rows, precision):
    """Attention of the prompt (P, d) and of each row (N, d) after it:
    (y_p, [y_row, ...])."""
    h, hk, d = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    n = x_p.shape[0]

    def heads_of(x):
        """q (Hk, T, G, D), k and v (Hk, T, D), by key-value head."""
        t = x.shape[0]
        q = mm(x, p["wq"], precision).reshape(t, hk, h // hk, d)
        k = mm(x, p["wk"], precision).reshape(t, hk, d)
        v = mm(x, p["wv"], precision).reshape(t, hk, d)
        return (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                jnp.moveaxis(v, 1, 0))

    def one_head(parts):
        (q, k, v), *rows = parts
        outs = [attend(q, k, v, 0, None, d ** -0.5, precision)]
        for q_r, k_r, v_r in rows:
            outs.append(attend(q_r, jnp.concatenate([k, k_r]),
                               jnp.concatenate([v, v_r]), n, None,
                               d ** -0.5, precision))
        return outs

    xs = [x_p] + list(x_rows)
    outs = jax.lax.map(one_head, [heads_of(x) for x in xs])
    ys = []
    for x, o in zip(xs, outs):                  # o (Hk, T, G, D)
        t = x.shape[0]
        gate = jax.nn.sigmoid(mm(x, p["wgate"], precision))  # (T, H x D)
        o = jnp.moveaxis(o, 0, 1).reshape(t, -1) * gate
        ys.append(mm(o, p["wo"], precision))
    return ys[0], ys[1:]


# ---- the stack -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ckey", "gqa", "precision"))
def _attn_block(norm, p, x_p, x_rows, ckey, gqa, precision):
    """x + attention(rmsnorm(x)) over the prompt and each row: one
    program a layer kind (a layer's many small operations dispatched one
    by one take minutes on the chip)."""
    c = dict(ckey)
    eps = c["rms_norm_eps"]
    y_p, y_rows = (softmax_layer if gqa else kda_layer)(
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
                                  x_rows, ckey, i in c["gqa_layers"],
                                  precision)
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
