"""Plain reference of a Ling-3.0-flash-class decoder: token ids in, logits
out. The yardstick of the ``textgen`` kind (``perfbench/kinds/textgen.py``).

Straight ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``; no module of the program, no cache, no absorbed
form, no chunks: the delta-rule recurrence runs token by token
(``lax.scan``), latent attention up-projects every key and value and
attends over all of them under a causal mask, and the experts are a loop
over the experts held. It reads its sizes from a plain dict of the
published ``config.json`` keys (``sizes`` below makes it from a
configuration file) and the weights by the names of the checkpoint
layout, which is all it shares with the program.

The layer equations (normed input ``x``, pre-norm residual blocks,
RMSNorm):

- KDA: q~, k~, v~ = W x, each through a depthwise causal conv over time
  (kernel 4) and SiLU; q = l2norm_head(q~) / sqrt(d_k), k =
  l2norm_head(k~) (l2norm(z) = z / sqrt(sum z^2 + 1e-6)); per head and
  channel log a_t = lower_bound * sigmoid(exp(A_log_h) * (W_a x +
  dt_bias)); b_t = sigmoid(W_b x) per head; S_t = (I - b_t k_t k_t^T)
  Diag(a_t) S_{t-1} + b_t k_t v_t^T; o_t = S_t^T q_t; y = W_o
  [rmsnorm_head(o_t) * sigmoid(W_g x)].
- MLA: q = W_q x -> heads x (nope + rope); [c, k_r] = W_dkv x; c =
  rmsnorm(c); [k_n, v] = W_ukv c; rotate-half RoPE on q's and k_r's rope
  part (k_r shared by the heads); softmax((q_n k_n + q_r k_r) / sqrt(nope
  + rope)) causal; y = W_o [o_h * sigmoid(w_h x)].
- experts: s = sigmoid(W_r x) over all experts; chosen by s + bias: the
  best ``topk_group`` groups by the sum of their two best, then the best
  K inside them; weights s of the chosen / their sum x the scaling
  factor; y = shared(x) + sum over the chosen experts HELD of w_e
  expert_e(x). What the absent ones would add is left out.

A job's rows share their prompt, so one pass serves several rows
(``forward_tree``): every per-token operation runs over the prompt once
and over each row's own tokens; a row's recurrence starts from the state
after the prompt, and its attention sees the prompt's keys and its own.
That is the same function of the same tokens as ``forward`` over prompt +
row (``tests/bench/test_bench_textgen.py`` holds the two equal).

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

from perfbench.reference import HIGHEST, _round, silu

#: the published keys the reference reads (others in a configuration's
#: file say nothing about these layers)
KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "layer_group_size", "num_attention_heads", "head_dim",
        "short_conv_kernel_size", "kda_lower_bound", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor", "rms_norm_eps")


def sizes(config: dict) -> dict:
    """The reference's view of a configuration file: the published keys,
    ``router_outputs`` (the published expert count: the router is never
    cut) and ``experts_held`` [first, past the last]."""
    c = {key: config[key] for key in KEYS}
    published = config.get("published", {})
    c["router_outputs"] = int(published.get("num_experts",
                                            config["num_experts"]))
    c["experts_held"] = list(config.get("experts_held",
                                        [0, config["num_experts"]]))
    return c


def is_mla(c: dict, layer: int) -> bool:
    return (layer + 1) % c["layer_group_size"] == 0


def mm(x, w, precision):
    return jnp.dot(_round(x, precision), _round(w, precision),
                   precision=HIGHEST)


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def swiglu(p, x, precision):
    return mm(silu(mm(x, p["gate"], precision)) * mm(x, p["up"], precision),
              p["down"], precision)


def rope(x, positions, theta):
    """Rotate-half over the last axis; x (T, ..., D), positions (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.asarray(positions, jnp.float32)[:, None] * freq[None]
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2)
                          + angle.shape[1:])
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


# ---- KDA -----------------------------------------------------------------


def kda_operands(p, c, x, history, precision):
    """x (T, d); ``history`` {q, k, v: (K-1, H*D)} the pre-conv values of
    the tokens before (zeros at the start of a sequence). Returns the
    recurrence's operands and this sequence's own pre-conv values."""
    t = x.shape[0]
    h, d = c["num_attention_heads"], c["head_dim"]
    pre, out = {}, {}
    for name in "qkv":
        pre[name] = mm(x, p[f"w{name}"], precision)
        seq = jnp.concatenate([history[name], pre[name]])
        w = p[f"conv_{name}"].astype(jnp.float32)
        out[name] = silu(sum(seq[i:i + t] * w[i] for i in range(w.shape[0]))
                         ).reshape(t, h, d)

    def l2(z):
        return z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

    gate = (mm(x, p["wa"], precision) + p["dt_bias"]).reshape(t, h, d) \
        * jnp.exp(p["a_log"].astype(jnp.float32))[None, :, None]
    return {"q": l2(out["q"]) / d ** 0.5, "k": l2(out["k"]), "v": out["v"],
            "g": c["kda_lower_bound"] * jax.nn.sigmoid(gate),
            "b": jax.nn.sigmoid(mm(x, p["wb"], precision))}, pre


@jax.jit
def kda_scan(ops, state):
    """The recurrence, token by token. state (H, Dk, Dv)."""
    def step(s, x):
        s = s * jnp.exp(x["g"])[..., None]
        pred = jnp.einsum("hk,hkv->hv", x["k"], s, precision=HIGHEST)
        u = x["b"][:, None] * (x["v"] - pred)
        s = s + x["k"][..., None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", x["q"], s, precision=HIGHEST)

    state, o = jax.lax.scan(step, state, ops, unroll=8)
    return o, state


def kda_out(p, c, x, o, precision):
    o = rms(o, p["o_norm"], c["rms_norm_eps"]) \
        * jax.nn.sigmoid(mm(x, p["wg"], precision)).reshape(o.shape)
    return mm(o.reshape(o.shape[0], -1), p["wo"], precision)


def kda_layer(p, c, x_p, x_rows, precision):
    h, d = c["num_attention_heads"], c["head_dim"]
    k = c["short_conv_kernel_size"] - 1
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


# ---- MLA -----------------------------------------------------------------


def mla_qkv(p, c, x, positions, precision):
    t = x.shape[0]
    h, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope = c["qk_nope_head_dim"]
    q = mm(x, p["wq"], precision).reshape(t, h, -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions,
                                             c["rope_theta"])], -1)
    ckr = mm(x, p["wdkv"], precision)
    latent = rms(ckr[:, :rank], p["kv_norm"], c["rms_norm_eps"])
    k_r = rope(ckr[:, rank:], positions, c["rope_theta"])
    kv = mm(latent, p["wukv"], precision).reshape(t, h, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None], (t, h, k_r.shape[-1]))], -1)
    return q, k, kv[..., nope:]


def causal_attend(q, k, v, offset, scale, precision, block=256):
    """Query l sits at position offset + l among the keys. In blocks of
    queries (``lax.map``), so that the logits of a 16k prompt fit; the
    last block is padded with zero queries, whose rows are dropped."""
    n = q.shape[0]
    block = min(block, n)
    pad = -n % block
    q = _round(jnp.pad(q, ((0, pad), (0, 0), (0, 0))), precision)
    k, v = _round(k, precision), _round(v, precision)
    key_pos = jnp.arange(k.shape[0])

    def one(args):
        qb, start = args
        logits = jnp.einsum("lhd,shd->hls", qb, k, precision=HIGHEST) * scale
        q_pos = offset + start + jnp.arange(block)
        visible = key_pos[None, :] <= q_pos[:, None]
        weights = jax.nn.softmax(
            jnp.where(visible[None], logits, -jnp.inf), -1)
        return jnp.einsum("hls,shd->lhd", _round(weights, precision), v,
                          precision=HIGHEST)

    out = jax.lax.map(one, (q.reshape(-1, block, *q.shape[1:]),
                            jnp.arange(0, n + pad, block)))
    return out.reshape(-1, *out.shape[2:])[:n]


def mla_layer(p, c, x_p, x_rows, precision):
    n = x_p.shape[0]
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5

    def out(x, o):
        gate = jax.nn.sigmoid(mm(x, p["wgate"], precision))
        return mm((o * gate[..., None]).reshape(o.shape[0], -1), p["wo"],
                  precision)

    q, k, v = mla_qkv(p, c, x_p, np.arange(n), precision)
    y_p = out(x_p, causal_attend(q, k, v, 0, scale, precision))
    y_rows = []
    for x in x_rows:
        q_r, k_r, v_r = mla_qkv(p, c, x, n + np.arange(x.shape[0]),
                                precision)
        o = causal_attend(q_r, jnp.concatenate([k, k_r]),
                          jnp.concatenate([v, v_r]), n, scale, precision)
        y_rows.append(out(x, o))
    return y_p, y_rows


# ---- experts ---------------------------------------------------------------


def route(p, c, x):
    """(chosen (T, K), weights (T, K)) over ALL experts, float32."""
    t = x.shape[0]
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    p["router"].astype(jnp.float32),
                                    precision=HIGHEST))
    choose = (scores + p["router_bias"]).reshape(t, c["n_group"], -1)
    group_score = jnp.sum(jnp.sort(choose, -1)[..., -2:], -1)
    best = jnp.argsort(-group_score, -1, stable=True)[:, :c["topk_group"]]
    keep = jnp.zeros((t, c["n_group"]), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    masked = jnp.where(keep[..., None], choose, -jnp.inf)
    chosen = jnp.argsort(-masked.reshape(t, -1), -1, stable=True)[
        :, :c["num_experts_per_tok"]]
    weight = jnp.take_along_axis(scores, chosen, -1)
    weight = weight / weight.sum(-1, keepdims=True) \
        * c["routed_scaling_factor"]
    return chosen, weight


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert_add(y, x_pad, experts, e, index, w, precision):
    """y[index] += w * expert_e(x_pad[index]); one program for each
    padded count of rows, whichever expert."""
    one = {name: jax.lax.dynamic_index_in_dim(mat, e, keepdims=False)
           for name, mat in experts.items()}
    return y.at[index].add(w[:, None] * swiglu(one, x_pad[index], precision))


@functools.partial(jax.jit, static_argnames=("ckey",))
def _route(router, router_bias, x, ckey):
    return route({"router": router, "router_bias": router_bias},
                 dict(ckey), x)


@functools.partial(jax.jit, static_argnames=("precision",))
def _with_shared(y, shared, x, precision):
    return y + swiglu(shared, x, precision)


def moe_layer(p, c, x, precision, held=None, shared=True, pad=128):
    """x (T, d) -> shared expert + the held experts' weighted outputs.
    ``p["experts"]`` holds the experts ``held`` = [first, past the last)
    in that order. A loop over the experts: each takes the tokens routed
    to it (their count padded up to a multiple of ``pad`` with a dummy
    row of weight 0, so that the products come in few shapes)."""
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
    return _with_shared(y[:t], p["shared"], x, precision) if shared \
        else y[:t]


# ---- the stack -------------------------------------------------------------


def _frozen(c: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()))


@functools.partial(jax.jit, static_argnames=("ckey", "mla", "precision"))
def _attn_block(norm, p, x_p, x_rows, ckey, mla, precision):
    """x + attention(rmsnorm(x)) over the prompt and each row: one
    program a layer kind (a layer's many small operations dispatched one
    by one take minutes on the chip)."""
    c = dict(ckey)
    eps = c["rms_norm_eps"]
    y_p, y_rows = (mla_layer if mla else kda_layer)(
        p, c, rms(x_p, norm, eps), [rms(x, norm, eps) for x in x_rows],
        precision)
    return x_p + y_p, [x + y for x, y in zip(x_rows, y_rows)]


@functools.partial(jax.jit, static_argnames=("ckey", "precision"))
def _dense_block(norm, p, x, ckey, precision):
    return x + swiglu(p, rms(x, norm, dict(ckey)["rms_norm_eps"]), precision)


@functools.partial(jax.jit, static_argnames=("ckey",))
def _normed(norm, x, ckey):
    return rms(x, norm, dict(ckey)["rms_norm_eps"])


def hidden_tree(params, c: dict, prompt, rows, precision):
    """Final hidden states of the prompt (P, d) and of each row (N, d)."""
    prompt, rows = np.asarray(prompt), np.asarray(rows)
    n_p, ckey = len(prompt), _frozen(c)
    embed = params["embed"]
    x_p = embed[prompt].astype(jnp.float32)
    x_rows = [embed[row].astype(jnp.float32) for row in rows]
    for i, layer in enumerate(params["layers"]):
        x_p, x_rows = _attn_block(layer["attn_norm"], layer["attn"], x_p,
                                  x_rows, ckey, is_mla(c, i), precision)
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


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_block(norm, head, x, eps, precision):
    return mm(rms(x, norm, eps), head, precision)


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


def token_logprobs(logits, tokens):
    """log softmax(logits)[token] per position; float64 on the host."""
    logits = np.asarray(logits, np.float64)
    top = logits.max(-1, keepdims=True)
    norm = top[..., 0] + np.log(np.exp(logits - top).sum(-1))
    picked = np.take_along_axis(logits, np.asarray(tokens)[..., None],
                                -1)[..., 0]
    return picked - norm
