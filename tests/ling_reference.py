"""The tests' own plain reference of the Ling-3.0-flash-class decoder:
one sequence of token ids in, float32 logits after every token out.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
with no cache, no absorbed form and no chunks: the KDA recurrence runs
token by token in a Python-visible ``lax.scan``, MLA up-projects every key
and value and attends over the whole sequence under a causal mask, the
experts are a loop. It takes the program's checkpoint layout
(``models/ling.py::param_shapes``) and a plain dict of sizes, and shares
no code with ``chiaswarm_tpu`` or with the benchmark's copy
(``perfbench/textref.py``; ``tests/bench/test_bench_textgen.py`` holds
the two equal). The equations are written out in that copy's docstring.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sizes_of(cfg) -> dict:
    """A plain dict of the sizes from any object that names them as the
    published ``config.json`` does."""
    names = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
             "layer_group_size", "num_attention_heads", "head_dim",
             "short_conv_kernel_size", "kda_lower_bound", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "rope_theta", "num_experts_per_tok", "n_group", "topk_group",
             "routed_scaling_factor", "rms_norm_eps", "experts_held")
    return {name: getattr(cfg, name) for name in names}


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def swiglu(p, x):
    return (silu(x @ f32(p["gate"])) * (x @ f32(p["up"]))) @ f32(p["down"])


def rope(x, theta):
    """x (T, ..., D) at positions 0..T-1, rotate-half."""
    t, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def kda_recurrence(q, k, v, g, b, state=None):
    """S_t = (I - b k k^T) Diag(e^g) S + b k v^T; o_t = S_t^T q_t.
    q, k, v, g (T, H, D), b (T, H) -> (o (T, H, D), final state)."""
    h, d = q.shape[1:]
    state = jnp.zeros((h, d, d), jnp.float32) if state is None else state

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s
        s = s - bt[:, None, None] * kt[..., None] * jnp.einsum(
            "hk,hkv->hv", kt, s)[:, None, :] \
            + bt[:, None, None] * kt[..., None] * vt[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s)

    state, o = jax.lax.scan(step, state, (q, k, v, g, b))
    return o, state


def kda_layer(p, c, x):
    t = x.shape[0]
    h, d = c["num_attention_heads"], c["head_dim"]
    kernel = c["short_conv_kernel_size"]

    def conv(name):
        pre = jnp.concatenate([jnp.zeros((kernel - 1, h * d)),
                               x @ f32(p[f"w{name}"])])
        w = f32(p[f"conv_{name}"])
        return silu(sum(pre[i:i + t] * w[i] for i in range(kernel))
                    ).reshape(t, h, d)

    def l2(z):
        return z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

    q, k, v = l2(conv("q")) / d ** 0.5, l2(conv("k")), conv("v")
    gate = (x @ f32(p["wa"]) + p["dt_bias"]).reshape(t, h, d) \
        * jnp.exp(f32(p["a_log"]))[None, :, None]
    g = c["kda_lower_bound"] * sigmoid(gate)
    o, _ = kda_recurrence(q, k, v, g, sigmoid(x @ f32(p["wb"])))
    o = rms(o, p["o_norm"], c["rms_norm_eps"]) \
        * sigmoid(x @ f32(p["wg"])).reshape(t, h, d)
    return o.reshape(t, -1) @ f32(p["wo"])


def mla_layer(p, c, x):
    t = x.shape[0]
    h, rank, nope = (c["num_attention_heads"], c["kv_lora_rank"],
                     c["qk_nope_head_dim"])
    q = (x @ f32(p["wq"])).reshape(t, h, -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:],
                                             c["rope_theta"])], -1)
    ckr = x @ f32(p["wdkv"])
    latent = rms(ckr[:, :rank], p["kv_norm"], c["rms_norm_eps"])
    k_r = rope(ckr[:, rank:], c["rope_theta"])
    kv = (latent @ f32(p["wukv"])).reshape(t, h, -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r[:, None],
                                          (t, h, k_r.shape[-1]))], -1)
    v = kv[..., nope:]
    scores = jnp.einsum("lhd,shd->hls", q, k) / q.shape[-1] ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    o = jnp.einsum("hls,shd->lhd", jax.nn.softmax(scores, -1), v)
    o = o * sigmoid(x @ f32(p["wgate"]))[..., None]
    return o.reshape(t, -1) @ f32(p["wo"])


def route(p, c, x):
    """(chosen experts (T, K), weights (T, K)) over ALL experts."""
    scores = np.asarray(sigmoid(x @ f32(p["router"])))
    choose = scores + np.asarray(p["router_bias"])
    t, n = choose.shape
    per = n // c["n_group"]
    chosen = np.zeros((t, c["num_experts_per_tok"]), np.int64)
    for row in range(t):
        groups = choose[row].reshape(c["n_group"], per)
        group_score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-group_score, kind="stable")[:c["topk_group"]]
        masked = np.full(n, -np.inf)
        for g in kept:
            masked[g * per:(g + 1) * per] = choose[row, g * per:(g + 1) * per]
        chosen[row] = np.argsort(-masked, kind="stable")[
            :c["num_experts_per_tok"]]
    weight = np.take_along_axis(scores, chosen, -1)
    weight = weight / weight.sum(-1, keepdims=True) \
        * c["routed_scaling_factor"]
    return chosen, weight


def moe_layer(p, c, x, held=None, shared=True):
    """Shared expert + the weighted outputs of the chosen experts that
    are ``held`` = [first, past the last); ``p["experts"]`` holds exactly
    those, in order."""
    first, past = c["experts_held"] if held is None else held
    chosen, weight = route(p, c, x)
    y = jnp.zeros_like(x)
    for e in range(first, past):
        w_e = jnp.asarray(np.where(chosen == e, weight, 0.0).sum(-1),
                          jnp.float32)
        one = {name: mat[e - first] for name, mat in p["experts"].items()}
        y = y + w_e[:, None] * swiglu(one, x)
    return y + swiglu(p["shared"], x) if shared else y


def forward(params, c, ids):
    """Logits (T, V) after every token of ``ids`` (T,)."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[np.asarray(ids)]
        eps = c["rms_norm_eps"]
        for i, layer in enumerate(params["layers"]):
            h = rms(x, layer["attn_norm"], eps)
            mla = (i + 1) % c["layer_group_size"] == 0
            x = x + (mla_layer if mla else kda_layer)(layer["attn"], c, h)
            h = rms(x, layer["mlp_norm"], eps)
            if i >= c["first_k_dense_replace"]:
                x = x + moe_layer(layer["mlp"], c, h)
            else:
                x = x + swiglu(layer["mlp"], h)
        return rms(x, params["final_norm"], eps) @ f32(params["head"])
