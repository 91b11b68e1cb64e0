"""The tests' own plain reference of the DeepSeek-V2-class decoder: one
sequence of token ids in, float32 logits after every token out.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
with no cache, no absorbed form, no chunks and no kernel: every layer
up-projects every key and value and attends over the whole sequence
under a causal mask, the experts are a loop. It takes the program's
checkpoint layout (``models/deepseek.py::param_shapes``) and a plain
dict of sizes, and shares no code with ``chiaswarm_tpu`` or with the
benchmark's copy (``perfbench/deepseekref.py``;
``tests/bench/test_bench_deepseek.py`` holds the two equal). The
equations are written out in that copy's docstring.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
         "num_experts_per_tok", "n_group", "topk_group",
         "routed_scaling_factor", "rms_norm_eps", "experts_held")
YARN = ("factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
        "original_max_position_embeddings")


def sizes_of(cfg) -> dict:
    """A plain dict of the sizes from any object that names them as the
    published ``config.json`` does (``rope_scaling`` a nested group)."""
    c = {name: getattr(cfg, name) for name in NAMES}
    c["rope_scaling"] = {name: getattr(cfg.rope_scaling, name)
                         for name in YARN}
    return c


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def swiglu(p, x):
    return (silu(x @ f32(p["gate"])) * (x @ f32(p["up"]))) @ f32(p["down"])


def mscale_of(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(c):
    """(frequencies (d/2,), what cos and sin are scaled by, the softmax
    scale) of the config's YaRN group."""
    y, dim, theta = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]

    def pair_with(turns):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_with(y["beta_fast"])), 0)
    high = min(math.ceil(pair_with(y["beta_slow"])), dim - 1)
    freq = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        keep = 1.0 - min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        freq.append(f / y["factor"] * (1.0 - keep) + f * keep)
    m = mscale_of(y["factor"], y["mscale_all_dim"])
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (np.asarray(freq, np.float32),
            mscale_of(y["factor"], y["mscale"]) / m, width ** -0.5 * m * m)


def rope(x, freq, amplitude):
    """x (T, ..., D) at positions 0..T-1, rotate-half."""
    t, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla_layer(p, c, x):
    t = x.shape[0]
    h, rank, nope = (c["num_attention_heads"], c["kv_lora_rank"],
                     c["qk_nope_head_dim"])
    freq, amplitude, scale = yarn(c)
    c_q = rms(x @ f32(p["wdq"]), p["q_norm"], c["rms_norm_eps"])
    q = (c_q @ f32(p["wuq"])).reshape(t, h, -1)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], freq, amplitude)], -1)
    ckr = x @ f32(p["wdkv"])
    latent = rms(ckr[:, :rank], p["kv_norm"], c["rms_norm_eps"])
    k_r = rope(ckr[:, rank:], freq, amplitude)
    kv = (latent @ f32(p["wukv"])).reshape(t, h, -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r[:, None],
                                          (t, h, k_r.shape[-1]))], -1)
    v = kv[..., nope:]
    scores = jnp.einsum("lhd,shd->hls", q, k) * scale
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    o = jnp.einsum("hls,shd->lhd", jax.nn.softmax(scores, -1), v)
    return o.reshape(t, -1) @ f32(p["wo"])


def route(p, c, x):
    """(chosen experts (T, K), weights (T, K)) over ALL experts."""
    logits = np.asarray(x @ f32(p["router"]), np.float64)
    scores = np.exp(logits - logits.max(-1, keepdims=True))
    scores = (scores / scores.sum(-1, keepdims=True)).astype(np.float32)
    t, n = scores.shape
    per = n // c["n_group"]
    chosen = np.zeros((t, c["num_experts_per_tok"]), np.int64)
    for row in range(t):
        group_score = scores[row].reshape(c["n_group"], per).max(-1)
        kept = np.argsort(-group_score, kind="stable")[:c["topk_group"]]
        masked = np.zeros(n, np.float32)
        for g in kept:
            masked[g * per:(g + 1) * per] = scores[row, g * per:(g + 1) * per]
        chosen[row] = np.argsort(-masked, kind="stable")[
            :c["num_experts_per_tok"]]
    weight = np.take_along_axis(scores, chosen, -1) \
        * c["routed_scaling_factor"]
    return chosen, weight


def moe_layer(p, c, x, held=None, shared=True):
    """Shared experts + the weighted outputs of the chosen experts that
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
            x = x + mla_layer(layer["attn"], c,
                              rms(x, layer["attn_norm"], eps))
            h = rms(x, layer["mlp_norm"], eps)
            if i >= c["first_k_dense_replace"]:
                x = x + moe_layer(layer["mlp"], c, h)
            else:
                x = x + swiglu(layer["mlp"], h)
        return rms(x, params["final_norm"], eps) @ f32(params["head"])
