"""The tests' own plain reference of the Laguna-XS.2-class decoder: one
sequence of token ids in, float32 logits after every token out.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
with no cache, no chunks, no groups of heads and no kernel: every layer
computes every head's scores over the whole sequence under a dense mask
(causal, and the window's where the layer has one), the experts are a
loop. It takes the program's checkpoint layout
(``models/laguna.py::param_shapes``) and a plain dict of sizes, and
shares no code with ``chiaswarm_tpu`` or with the benchmark's copy
(``perfbench/lagunaref.py``; ``tests/bench/test_bench_laguna.py`` holds
the two equal). The equations are written out in that copy's docstring.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("hidden_size", "num_hidden_layers", "num_key_value_heads",
         "head_dim", "layer_types", "num_attention_heads_per_layer",
         "mlp_layer_types", "sliding_window", "num_experts_per_tok",
         "moe_routed_scaling_factor", "rms_norm_eps", "experts_held")
ROPE = ("rope_theta", "partial_rotary_factor", "rope_type", "factor",
        "original_max_position_embeddings", "beta_fast", "beta_slow",
        "attention_factor")


def sizes_of(cfg) -> dict:
    """A plain dict of the sizes from any object that names them as the
    published ``config.json`` does (``rope_parameters`` a nested group
    with one group a layer type)."""
    c = {name: getattr(cfg, name) for name in NAMES}
    c["rope_parameters"] = {
        kind: {name: getattr(getattr(cfg.rope_parameters, kind), name)
               for name in ROPE}
        for kind in ("full_attention", "sliding_attention")}
    return c


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def swiglu(p, x):
    return (silu(x @ f32(p["gate"])) * (x @ f32(p["up"]))) @ f32(p["down"])


def frequencies(c, kind):
    """(frequencies of the rotated values (as many as half of them), what
    cos and sin are scaled by) of a layer type."""
    r = c["rope_parameters"][kind]
    dim, theta = int(c["head_dim"] * r["partial_rotary_factor"]), \
        r["rope_theta"]
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if r["rope_type"] != "yarn":
        return np.asarray(plain, np.float32), 1.0

    def pair_with(turns):
        return dim * math.log(r["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_with(r["beta_fast"])), 0)
    high = min(math.ceil(pair_with(r["beta_slow"])), dim - 1)
    freq = []
    for i, f in enumerate(plain):
        keep = 1.0 - min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        freq.append(f / r["factor"] * (1.0 - keep) + f * keep)
    return np.asarray(freq, np.float32), r["attention_factor"]


def rope(x, freq, amplitude):
    """x (T, H, D) at positions 0..T-1: rotate-half over the first
    ``2 x len(freq)`` values of every head, the rest as they are."""
    t, width = x.shape[0], 2 * len(freq)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    a, b = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., width:]], -1)


def attention_layer(p, c, layer, x):
    t = x.shape[0]
    kind = c["layer_types"][layer]
    h, hk, d = (c["num_attention_heads_per_layer"][layer],
                c["num_key_value_heads"], c["head_dim"])
    freq, amplitude = frequencies(c, kind)
    q = rope((x @ f32(p["wq"])).reshape(t, h, d), freq, amplitude)
    k = rope((x @ f32(p["wk"])).reshape(t, hk, d), freq, amplitude)
    v = (x @ f32(p["wv"])).reshape(t, hk, d)
    # query head j reads key-value head j // (h / hk)
    k, v = (jnp.repeat(z, h // hk, axis=1) for z in (k, v))
    scores = jnp.einsum("lhd,shd->hls", q, k) * d ** -0.5
    row, col = np.arange(t)[:, None], np.arange(t)[None, :]
    visible = col <= row
    if kind == "sliding_attention":
        visible &= col > row - c["sliding_window"]
    scores = jnp.where(visible, scores, -jnp.inf)
    o = jnp.einsum("hls,shd->lhd", jax.nn.softmax(scores, -1), v)
    gate = 1.0 / (1.0 + jnp.exp(-(x @ f32(p["wg"]))))         # (T, H)
    return (o * gate[..., None]).reshape(t, -1) @ f32(p["wo"])


def route(p, c, x):
    """(chosen experts (T, K), weights (T, K)) over ALL experts: softmax,
    the K largest (ties to the lower index), normalised to sum 1, times
    the scaling factor."""
    logits = np.asarray(x @ f32(p["router"]), np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = (probs / probs.sum(-1, keepdims=True)).astype(np.float32)
    chosen = np.argsort(-probs, -1, kind="stable")[
        :, :c["num_experts_per_tok"]]
    weight = np.take_along_axis(probs, chosen, -1)
    weight = weight / weight.sum(-1, keepdims=True) \
        * c["moe_routed_scaling_factor"]
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
            x = x + attention_layer(layer["attn"], c, i,
                                    rms(x, layer["attn_norm"], eps))
            h = rms(x, layer["mlp_norm"], eps)
            if c["mlp_layer_types"][i] == "sparse":
                x = x + moe_layer(layer["mlp"], c, h)
            else:
                x = x + swiglu(layer["mlp"], h)
        return rms(x, params["final_norm"], eps) @ f32(params["head"])
