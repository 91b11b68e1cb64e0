"""Operations one job needs, from the configuration's widths alone.

Multiply-adds count as two operations. Only what the algorithm needs
counts: one UNet evaluation per step for each of the CFG pair, two text
encodes (prompt and empty negative), one VAE decode. Padding rows,
cached or skipped work and recomputation never enter, so ``step_mfu``
reads the same for the same jobs whatever implements them.
"""

from __future__ import annotations

from perfbench.reference import unet_depths, unet_heads


def conv(h, w, cin, cout, k=3):
    return 2.0 * h * w * cin * cout * k * k


def dense(tokens, cin, cout):
    return 2.0 * tokens * cin * cout


def attention(l, s, inner):
    """QK^T and PV."""
    return 4.0 * l * s * inner


def resnet(h, w, cin, cout, temb=0):
    total = conv(h, w, cin, cout) + conv(h, w, cout, cout)
    if temb:
        total += dense(1, temb, cout)
    if cin != cout:
        total += conv(h, w, cin, cout, k=1)
    return total


def transformer(h, w, c, depth, ctx_len, ctx_dim):
    l = h * w
    block = (4 * dense(l, c, c) + attention(l, l, c)            # self
             + 2 * dense(l, c, c) + 2 * dense(ctx_len, ctx_dim, c)
             + attention(l, ctx_len, c)                          # cross
             + dense(l, c, 8 * c) + dense(l, 4 * c, c))          # GEGLU
    return 2 * dense(l, c, c) + depth * block


def unet_forward(config: dict, height: int, width: int) -> float:
    """One sample through the UNet at an image size."""
    u = config["unet"]
    ctx_len = config["text_encoders"][0]["max_position_embeddings"]
    f = 2 ** (len(config["vae"]["block_out_channels"]) - 1)
    h, w = height // f, width // f
    chans = u["block_out_channels"]
    depths = unet_depths(u)
    n_res = u["layers_per_block"]
    ctx = u["cross_attention_dim"]
    temb = chans[0] * 4
    total = dense(1, chans[0], temb) + dense(1, temb, temb)
    if u.get("addition_time_embed_dim"):
        total += dense(1, u["projection_class_embeddings_input_dim"], temb) \
            + dense(1, temb, temb)
    total += conv(h, w, u["in_channels"], chans[0])
    skips = [chans[0]]
    x = chans[0]
    for level, ch in enumerate(chans):
        for _ in range(n_res):
            total += resnet(h, w, x, ch, temb)
            x = ch
            if depths[level]:
                total += transformer(h, w, ch, depths[level], ctx_len, ctx)
            skips.append(x)
        if level < len(chans) - 1:
            h, w = h // 2, w // 2
            total += conv(h, w, ch, ch)
            skips.append(x)
    total += 2 * resnet(h, w, x, x, temb) + transformer(
        h, w, x, max(depths) or 1, ctx_len, ctx)
    for level in range(len(chans) - 1, -1, -1):
        ch = chans[level]
        for _ in range(n_res + 1):
            total += resnet(h, w, x + skips.pop(), ch, temb)
            x = ch
            if depths[level]:
                total += transformer(h, w, ch, depths[level], ctx_len, ctx)
        if level > 0:
            h, w = h * 2, w * 2
            total += conv(h, w, ch, ch)
    return total + conv(h, w, chans[0], u["out_channels"])


def text_encode(config: dict) -> float:
    """One prompt through every text tower."""
    total = 0.0
    for t in config["text_encoders"]:
        n, d, ff = (t["max_position_embeddings"], t["hidden_size"],
                    t["intermediate_size"])
        total += t["num_hidden_layers"] * (
            4 * dense(n, d, d) + attention(n, n, d) + 2 * dense(n, d, ff))
        if t.get("with_projection"):
            total += dense(1, d, t["projection_dim"])
    return total


def vae_decode(config: dict, height: int, width: int) -> float:
    v = config["vae"]
    chans = v["block_out_channels"]
    f = 2 ** (len(chans) - 1)
    h, w = height // f, width // f
    top = chans[-1]
    lat = v["latent_channels"]
    total = conv(h, w, lat, lat, k=1) + conv(h, w, lat, top)
    total += 2 * resnet(h, w, top, top) + 4 * dense(h * w, top, top) \
        + attention(h * w, h * w, top)
    x = top
    for level in range(len(chans) - 1, -1, -1):
        ch = chans[level]
        for _ in range(v["layers_per_block"] + 1):
            total += resnet(h, w, x, ch)
            x = ch
        if level > 0:
            h, w = h * 2, w * 2
            total += conv(h, w, ch, ch)
    return total + conv(h, w, x, v["out_channels"])


def job(config: dict, steps: int, height: int, width: int) -> float:
    """One txt2img job under classifier-free guidance."""
    return (steps * 2 * unet_forward(config, height, width)
            + 2 * text_encode(config) + vae_decode(config, height, width))


def attention_sites(config: dict, height: int, width: int) -> list[tuple]:
    """(query tokens, key tokens, head size) of every attention the
    configuration has at an image size, as the algorithm states them:
    the UNet's self- and cross-attentions level by level and the VAE's
    one. What a flash call's padded operands are costed by (hlo.py)."""
    u, v = config["unet"], config["vae"]
    ctx_len = config["text_encoders"][0]["max_position_embeddings"]
    f = 2 ** (len(v["block_out_channels"]) - 1)
    h, w = height // f, width // f
    sites = {(h * w, h * w, v["block_out_channels"][-1])}
    chans = u["block_out_channels"]
    depths = unet_depths(u)
    for level, ch in enumerate(chans):
        head = ch // unet_heads(u, level)
        if depths[level]:
            sites |= {(h * w, h * w, head), (h * w, ctx_len, head)}
        if level < len(chans) - 1:
            h, w = h // 2, w // 2
    head = chans[-1] // unet_heads(u, len(chans) - 1)
    sites |= {(h * w, h * w, head), (h * w, ctx_len, head)}  # mid block
    return sorted(sites)
