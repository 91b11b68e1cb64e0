"""Plain reference of one txt2img job: prompt, seed, steps -> pixels.

The yardstick that decides ``correct``. Straight ``jax.numpy`` in float32
with every matmul and convolution at ``Precision.HIGHEST``: no kernels,
no cache, no batching beyond the CFG pair, no module of the program.
It follows the published descriptions (CLIP text towers, the Stable
Diffusion UNet / SDXL UNet, AutoencoderKL decoder, DPM-Solver++(2M) on
Karras sigmas with classifier-free guidance) and reads every size from
the configuration's own file (``perfbench/configs/<name>.json``).

What it shares with the program is the *checkpoint layout* only: the
weights are made by ``perfbench/weights.py`` from ``--seed`` (the program
is handed the same arrays as its checkpoint), and this file reads them by
the parameter names of that layout.

Departures from the published pipelines, each matched to what the served
job states: the tokenizer is the FNV-1a word hash the repo serves random
checkpoints with (no vocabulary file exists here), so the traffic's
prompts are lower-case a-z words; the initial noise is
``jax.random.normal`` under the job's seed (threefry), row 0.

``precision`` selects how the operands of every matmul/convolution are
rounded before the float32 product:

- ``"float32"``: not at all (the reference);
- ``"bfloat16"``: to bfloat16 (what the configurations state; a sanity
  reading, never compared by the benchmark);
- ``"fp8"``: to float8_e4m3fn under a per-tensor amax scale, the nearest
  precision below bfloat16 - the control that ``correct`` has to fail.
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0


# ---- operand rounding (the control's seam) ------------------------------


def _round(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / _FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown precision {precision!r}")


def dense(p, x, precision, bias=True):
    y = jnp.dot(_round(x, precision), _round(p["kernel"], precision),
                precision=HIGHEST)
    if bias and "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def conv(p, x, precision, stride=1, padding=1):
    pad = [(padding, padding)] * 2 if isinstance(padding, int) else padding
    y = jax.lax.conv_general_dilated(
        _round(x, precision), _round(p["kernel"], precision),
        window_strides=(stride, stride), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + p["bias"].astype(jnp.float32)


def attend(q, k, v, heads, precision):
    """softmax(q k^T / sqrt(d)) v over (B, L, heads*d) projections."""
    b, l, inner = q.shape
    s = k.shape[1]
    d = inner // heads
    q = _round(q, precision).reshape(b, l, heads, d)
    k = _round(k, precision).reshape(b, s, heads, d)
    v = _round(v, precision).reshape(b, s, heads, d)
    logits = jnp.einsum("blhd,bshd->bhls", q, k, precision=HIGHEST)
    weights = jax.nn.softmax(logits * d ** -0.5, axis=-1)
    out = jnp.einsum("bhls,bshd->blhd", _round(weights, precision), v,
                     precision=HIGHEST)
    return out.reshape(b, l, inner)


# ---- normalizations and activations -------------------------------------


def groups_of(channels: int) -> int:
    g = min(32, channels)
    while channels % g:
        g -= 1
    return g


def group_norm(p, x, eps):
    b, h, w, c = x.shape
    g = groups_of(c)
    xg = x.astype(jnp.float32).reshape(b, h * w, g, c // g)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    xn = ((xg - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return xn * p["scale"].astype(jnp.float32) \
        + p["bias"].astype(jnp.float32)


def layer_norm(p, x, eps=1e-5):
    x = x.astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) \
        * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def quick_gelu(x):
    return x * jax.nn.sigmoid(1.702 * x)


# ---- tokenizer -----------------------------------------------------------


def hash_tokens(prompt: str, vocab_size: int, eos: int, length: int):
    """[bos] + FNV-1a word ids + [eos], padded with eos (CLIP's pad)."""
    if re.search(r"[^a-z ]", prompt):
        raise ValueError(f"prompts are lower-case a-z words: {prompt!r}")
    bos = eos - 1
    lo, hi = 0, bos
    words = prompt.split()
    ids = [bos]
    for word in words[: length - 2]:
        h = 2166136261
        for ch in word.encode("utf-8"):
            h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
        ids.append(lo + h % max(hi - lo, 1))
    ids.append(eos)
    ids += [eos] * (length - len(ids))
    return np.asarray(ids[:length], np.int32)


# ---- CLIP text tower -----------------------------------------------------


def clip_text(p, ids, cfg, precision):
    """-> (sequence readout, pooled). ``cfg`` is the tower's group of the
    configuration file."""
    p = p["params"]
    n = ids.shape[0]
    heads = cfg["num_attention_heads"]
    act = {"quick_gelu": quick_gelu, "gelu": gelu_erf}[cfg["hidden_act"]]
    x = p["token_embedding"]["embedding"].astype(jnp.float32)[ids] \
        + p["position_embedding"]["embedding"].astype(jnp.float32)[:n]
    x = x[None]
    causal = jnp.triu(jnp.full((n, n), -jnp.inf, jnp.float32), k=1)
    hidden = []
    for i in range(cfg["num_hidden_layers"]):
        hidden.append(x)
        lp = p[f"layers_{i}"]
        h = layer_norm(lp["layer_norm1"], x)
        at = lp["self_attn"]
        q, k, v = (dense(at[name], h, precision)
                   for name in ("q_proj", "k_proj", "v_proj"))
        d = q.shape[-1] // heads
        qh, kh, vh = (_round(t, precision).reshape(1, n, heads, d)
                      for t in (q, k, v))
        logits = jnp.einsum("blhd,bshd->bhls", qh, kh, precision=HIGHEST)
        weights = jax.nn.softmax(logits * d ** -0.5 + causal, axis=-1)
        o = jnp.einsum("bhls,bshd->blhd", _round(weights, precision), vh,
                       precision=HIGHEST).reshape(1, n, -1)
        x = x + dense(at["out_proj"], o, precision)
        h = layer_norm(lp["layer_norm2"], x)
        x = x + dense(lp["fc2"], act(dense(lp["fc1"], h, precision)),
                      precision)
    hidden.append(x)
    final = layer_norm(p["final_layer_norm"], x)
    readout = hidden[cfg["output_layer"]] if cfg["output_layer"] != -1 else x
    seq = layer_norm(p["final_layer_norm"], readout) \
        if cfg["final_layer_norm"] else readout
    eos_at = jnp.argmax(ids == cfg["eos_token_id"])
    pooled = final[0, eos_at]
    if cfg.get("with_projection"):
        pooled = dense(p["text_projection"], pooled, precision, bias=False)
    return seq[0], pooled


def prompt_ids(prompt: str, config: dict) -> list[np.ndarray]:
    return [hash_tokens(prompt, tower["vocab_size"], tower["eos_token_id"],
                        tower["max_position_embeddings"])
            for tower in config["text_encoders"]]


def encode_prompt(params, ids_list, config, precision):
    """-> (context (77, cross_dim), pooled of the last tower)."""
    seqs, pooled = [], None
    for i, tower in enumerate(config["text_encoders"]):
        seq, pooled = clip_text(params[f"text_encoder_{i}"], ids_list[i],
                                tower, precision)
        seqs.append(seq)
    return jnp.concatenate(seqs, axis=-1), pooled


# ---- UNet ----------------------------------------------------------------


def sinusoid(values, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = values.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def time_mlp(p, x, precision):
    return dense(p["linear_2"], silu(dense(p["linear_1"], x, precision)),
                 precision)


def resnet(p, x, temb, precision, eps=1e-5):
    h = conv(p["conv1"], silu(group_norm(p["norm1"], x, eps)), precision)
    if temb is not None:
        h = h + dense(p["time_emb_proj"], silu(temb),
                      precision)[:, None, None, :]
    h = conv(p["conv2"], silu(group_norm(p["norm2"], h, eps)), precision)
    if "conv_shortcut" in p:
        x = conv(p["conv_shortcut"], x, precision, padding=0)
    return x + h


def cross_attention(p, x, context, heads, precision):
    context = x if context is None else context
    q = dense(p["to_q"], x, precision, bias=False)
    k = dense(p["to_k"], context, precision, bias=False)
    v = dense(p["to_v"], context, precision, bias=False)
    return dense(p["to_out"], attend(q, k, v, heads, precision), precision)


def transformer_block(p, x, context, heads, precision):
    x = x + cross_attention(p["attn1"], layer_norm(p["norm1"], x), None,
                            heads, precision)
    x = x + cross_attention(p["attn2"], layer_norm(p["norm2"], x), context,
                            heads, precision)
    h = dense(p["ff"]["proj_in"], layer_norm(p["norm3"], x), precision)
    h, gate = jnp.split(h, 2, axis=-1)
    return x + dense(p["ff"]["proj_out"], h * gelu_erf(gate), precision)


def spatial_transformer(p, x, context, depth, heads, linear, precision):
    b, hh, ww, c = x.shape
    residual = x
    x = group_norm(p["norm"], x, 1e-6)
    if linear:
        x = dense(p["proj_in"], x.reshape(b, hh * ww, c), precision)
    else:
        x = conv(p["proj_in"], x, precision, padding=0)
        x = x.reshape(b, hh * ww, c)
    for i in range(depth):
        x = transformer_block(p[f"transformer_blocks_{i}"], x, context,
                              heads, precision)
    if linear:
        x = dense(p["proj_out"], x, precision).reshape(b, hh, ww, c)
    else:
        x = conv(p["proj_out"], x.reshape(b, hh, ww, c), precision,
                 padding=0)
    return x + residual


def upsample2x(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def unet_heads(u, level):
    """Both published configs store the head COUNT under
    ``attention_head_dim`` (diffusers reads it as num_attention_heads)."""
    head = u["attention_head_dim"]
    return head[level] if isinstance(head, (list, tuple)) else head


def unet_depths(u):
    """Transformer blocks per level: the published per-level count where
    the level's down block type carries cross-attention, else none."""
    per = u.get("transformer_layers_per_block", 1)
    n = len(u["block_out_channels"])
    per = list(per) if isinstance(per, (list, tuple)) else [per] * n
    return [per[i] if "CrossAttn" in kind else 0
            for i, kind in enumerate(u["down_block_types"])]


def unet(p, u, sample, t, context, added, precision):
    """Model prediction for NHWC latents. ``u`` is the configuration's
    ``unet`` group; ``added`` is (time_ids, pooled text) for SDXL."""
    p = p["params"]
    chans = u["block_out_channels"]
    depths = unet_depths(u)
    linear = u["use_linear_projection"]
    n_res = u["layers_per_block"]
    temb = time_mlp(p["time_embedding"], sinusoid(t, chans[0]), precision)
    if u.get("addition_time_embed_dim"):
        time_ids, pooled = added
        ids_emb = sinusoid(time_ids.reshape(-1),
                           u["addition_time_embed_dim"]
                           ).reshape(time_ids.shape[0], -1)
        temb = temb + time_mlp(
            p["add_embedding"],
            jnp.concatenate([pooled.astype(jnp.float32), ids_emb], -1),
            precision)

    def attn(name, x, level, depth):
        return spatial_transformer(
            p[name], x, context, depth,
            unet_heads(u, level), linear, precision)

    x = conv(p["conv_in"], sample, precision)
    skips = [x]
    for level, ch in enumerate(chans):
        for j in range(n_res):
            x = resnet(p[f"down_{level}_resnets_{j}"], x, temb, precision)
            if depths[level]:
                x = attn(f"down_{level}_attentions_{j}", x, level,
                         depths[level])
            skips.append(x)
        if level < len(chans) - 1:
            x = conv(p[f"down_{level}_downsample"]["conv"], x, precision,
                     stride=2)
            skips.append(x)
    x = resnet(p["mid_resnets_0"], x, temb, precision)
    x = attn("mid_attention", x, len(chans) - 1, max(depths) or 1)
    x = resnet(p["mid_resnets_1"], x, temb, precision)
    for level in range(len(chans) - 1, -1, -1):
        for j in range(n_res + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = resnet(p[f"up_{level}_resnets_{j}"], x, temb, precision)
            if depths[level]:
                x = attn(f"up_{level}_attentions_{j}", x, level,
                         depths[level])
        if level > 0:
            x = conv(p[f"up_{level}_upsample"]["conv"], upsample2x(x),
                     precision)
    x = silu(group_norm(p["conv_norm_out"], x, 1e-5))
    return conv(p["conv_out"], x, precision)


# ---- VAE decoder ---------------------------------------------------------


def vae_decode(p, v, z, precision):
    """Scaled latents (1, h, w, 4) -> image in about [-1, 1]."""
    p = p["params"]["decoder"]
    chans = v["block_out_channels"]
    z = z / v["scaling_factor"]
    x = conv(p["post_quant_conv"], z, precision, padding=0)
    x = conv(p["conv_in"], x, precision)
    mid = p["mid"]
    x = resnet(mid["resnets_0"], x, None, precision, eps=1e-6)
    at = mid["attentions_0"]
    b, hh, ww, c = x.shape
    h = group_norm(at["group_norm"], x, 1e-6).reshape(b, hh * ww, c)
    q, k, vv = (dense(at[name], h, precision)
                for name in ("to_q", "to_k", "to_v"))
    h = dense(at["to_out"], attend(q, k, vv, 1, precision), precision)
    x = x + h.reshape(b, hh, ww, c)
    x = resnet(mid["resnets_1"], x, None, precision, eps=1e-6)
    for level in range(len(chans) - 1, -1, -1):
        for j in range(v["layers_per_block"] + 1):
            x = resnet(p[f"up_{level}_resnets_{j}"], x, None, precision,
                       eps=1e-6)
        if level > 0:
            x = conv(p[f"up_{level}_upsample"], upsample2x(x), precision)
    x = silu(group_norm(p["conv_norm_out"], x, 1e-6))
    return conv(p["conv_out"], x, precision)


# ---- sampler: DPM-Solver++(2M) on Karras sigmas --------------------------


def sigma_ladder(steps: int, sched: dict):
    """(sigmas (steps+1,), timesteps (steps,)) in float64 numpy: leading
    spacing with offset 1, Karras rho 7 between the ladder's ends, model
    timesteps by log-sigma interpolation."""
    n_train = sched["num_train_timesteps"]
    betas = np.linspace(sched["beta_start"] ** 0.5, sched["beta_end"] ** 0.5,
                        n_train, dtype=np.float64) ** 2
    if sched["beta_schedule"] != "scaled_linear":
        raise ValueError(sched["beta_schedule"])
    acp = np.cumprod(1.0 - betas)
    table = np.sqrt((1.0 - acp) / acp)
    ts = np.clip(np.arange(steps) * (n_train // steps)
                 + sched["steps_offset"], 0, n_train - 1)
    ends = np.interp(ts, np.arange(n_train), table)
    s_min, s_max = ends[0], ends[-1]
    ramp = np.linspace(0.0, 1.0, steps)
    rho = 7.0
    sig = (s_max ** (1 / rho) + ramp * (s_min ** (1 / rho)
                                        - s_max ** (1 / rho))) ** rho
    timesteps = np.interp(np.log(np.maximum(sig, 1e-10)), np.log(table),
                          np.arange(n_train, dtype=np.float64))
    return np.concatenate([sig, [0.0]]), timesteps


def dpmpp_2m_update(x, eps, old, i, sigmas):
    """One step on host-side sigma values (python floats)."""
    sigma, sigma_next = sigmas[i], sigmas[i + 1]
    denoised = x - sigma * eps
    if sigma_next == 0.0:
        return denoised, denoised
    t, t_next = -math.log(sigma), -math.log(sigma_next)
    h = t_next - t
    use = denoised
    if i > 0:
        r = (t + math.log(sigmas[i - 1])) / h
        use = (1.0 + 1.0 / (2.0 * r)) * denoised - (1.0 / (2.0 * r)) * old
    return (sigma_next / sigma) * x - math.expm1(-h) * use, denoised


# ---- one whole job -------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(config_key: str, precision: str):
    """The jitted pieces for one configuration and precision."""
    import json

    config = json.loads(config_key)

    @jax.jit
    def encode(params, ids_list):
        return encode_prompt(params, ids_list, config, precision)

    @jax.jit
    def step(params, x, ctx2, added, sigma, t, guidance):
        inp = x / jnp.sqrt(sigma ** 2 + 1.0)
        out = unet(params["unet"], config["unet"],
                   jnp.concatenate([inp, inp], axis=0),
                   jnp.stack([t, t]), ctx2, added, precision)
        eps_u, eps_c = out[:1], out[1:]
        return eps_u + guidance * (eps_c - eps_u)

    @jax.jit
    def decode(params, x):
        img = vae_decode(params["vae"], config["vae"], x, precision)
        return jnp.clip((img + 1.0) * 127.5, 0.0, 255.0)

    return encode, step, decode


def initial_noise(seed: int, lh: int, lw: int, channels: int):
    """Row 0 of a job's rows: fold the row index into the seed's key,
    split, draw from the second half."""
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) & 0x7FFF_FFFF_FFFF_FFFF), 0)
    return jax.random.normal(jax.random.split(key)[1], (lh, lw, channels),
                             jnp.float32)


def generate(params, config: dict, *, prompt: str, seed: int, steps: int,
             guidance: float, height: int, width: int,
             negative_prompt: str = "", precision: str = "float32"):
    """The job's image as float pixels in [0, 255], shape (H, W, 3)."""
    import json

    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    encode, step, decode = _programs(json.dumps(config, sort_keys=True),
                                     precision)
    ctx_c, pooled_c = encode(params, prompt_ids(prompt, config))
    ctx_u, pooled_u = encode(params, prompt_ids(negative_prompt, config))
    ctx2 = jnp.stack([ctx_u, ctx_c])
    added = None
    if config["unet"].get("addition_time_embed_dim"):
        time_ids = jnp.asarray(
            [[height, width, 0, 0, height, width]] * 2, jnp.float32)
        added = (time_ids, jnp.stack([pooled_u, pooled_c]))
    f = 2 ** (len(config["vae"]["block_out_channels"]) - 1)
    lh, lw = height // f, width // f
    sigmas, timesteps = sigma_ladder(steps, config["scheduler"])
    # committed to the weights' device like every later x (an
    # uncommitted first x would compile the step program a second time)
    device = next(iter(jax.tree.leaves(params["unet"]))).devices().pop()
    x = jax.device_put(
        initial_noise(seed, lh, lw, config["unet"]["in_channels"])[None]
        * jnp.float32(sigmas[0]), device)
    old = jnp.zeros_like(x)
    for i in range(steps):
        eps = step(params, x, ctx2, added, jnp.float32(sigmas[i]),
                   jnp.float32(timesteps[i]), jnp.float32(guidance))
        x, old = dpmpp_2m_update(x, eps, old, i, [float(s) for s in sigmas])
    return np.asarray(decode(params, x)[0])
