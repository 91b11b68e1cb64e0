"""The LFM2-MoE-class decoder (models/lfm2.py) against the benchmark's plain
float32 reference (perfbench/lfm2ref.py) on seeded weights at the tiny
size: hidden 128, the two dense conv layers and one period (A C C C),
attention of 8 query heads over 2 key-value heads of 16 with per-head
query and key norms and full rotary, 16 sigmoid-routed experts with a bias
and no shared expert, all held, a head tied to the embedding, float32
weights.

Tolerances. Program and reference compute the same function in float32
in another order (chunks against the whole sequence, grouped heads, a
shared prompt joined to a suffix, grouped experts), so they differ by
rounding: logits of magnitude ~3 agree to a few 1e-6. The limits sit a
decade above that, and the tests show that the same computation with
bfloat16-rounded operands (relative step 2^-8) misses its limit by a wide
margin: a lower precision cannot hide inside them.
"""

import asyncio
import base64
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models import lfm2, solar, text_layers, text_stacks
from chiaswarm_tpu.ops import causal_flash_attention as kernel
from perfbench import lfm2ref

CFG = lfm2.TINY
LOGIT_TOL = 5e-5      # |logit| ~ 3: a few float32 roundings, ~10x room
LAYER_TOL = 2e-5      # one layer's output, magnitude ~1
KERNEL_TOL = 5e-6     # one softmax read-out of unit values


def bf16(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def sizes_of(cfg):
    """The reference's view of a configuration, as a configuration file
    would hold it (``head_dim`` stated)."""
    return lfm2ref.sizes(dict(dataclasses.asdict(cfg), head_dim=cfg.head_dim))


@pytest.fixture(scope="module")
def params():
    return lfm2.random_params(CFG, seed=3)


@pytest.fixture(scope="module")
def sizes():
    return sizes_of(CFG)


def prefill(params, cfg, ids, chunk, capacity=64):
    caches = lfm2.empty_prefill_caches(cfg, capacity)
    fn = jax.jit(lambda p, i, c, pos, n: lfm2.prefill_chunk(
        p, cfg, i, c, pos, n))
    for pos in range(0, len(ids), chunk):
        part = np.zeros((1, chunk), np.int32)
        n = min(chunk, len(ids) - pos)
        part[0, :n] = ids[pos:pos + n]
        logits, caches, stats = fn(params, jnp.asarray(part), caches,
                                   jnp.int32(pos), jnp.int32(n))
    return logits, caches, stats


def published():
    """The cell's cut of the published configuration: layers 0-13, every
    expert, the whole vocabulary, bfloat16."""
    return dataclasses.replace(
        lfm2.Lfm2Config(), num_hidden_layers=14,
        layer_types=lfm2.LAYER_TYPES[:14])


def count(cfg):
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(lfm2.param_shapes(cfg)))


# ---- the preset and the layout ---------------------------------------------


def test_the_tiny_preset_holds_every_case_the_stack_has():
    assert CFG.layer_types == ("conv", "conv", "full_attention", "conv",
                               "conv", "conv")
    assert CFG.conv_layers == [0, 1, 3, 4, 5] and CFG.attention_layers == [2]
    assert [CFG.is_moe(i) for i in range(6)] == [False, False] + [True] * 4
    assert CFG.head_dim == 16 and CFG.rms_norm_eps == CFG.norm_eps
    assert CFG.num_attention_heads // CFG.num_key_value_heads == 4
    assert lfm2.LAYER_TYPES.count("full_attention") == 6
    assert [i for i, t in enumerate(lfm2.LAYER_TYPES)
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    for wrong in ({"conv_bias": True}, {"tie_word_embeddings": False},
                  {"use_expert_bias": False}, {"num_key_value_heads": 3},
                  {"layer_types": ("conv",) * 5},
                  {"layer_types": ("conv",) * 5 + ("sliding_attention",)}):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)


def test_the_layout_is_the_published_one_at_the_cut():
    """The benchmark's cut: layers 0-13 (two dense conv layers, three
    periods A C C C), all 32 experts and the whole vocabulary: 4.666 B
    parameters, 9.34 GB in bfloat16 (the routers float32); the whole
    model 8.34 B, tied (an untied head would make it 8.47 B)."""
    cfg = published()
    size = sum(int(np.prod(s.shape)) * s.dtype.itemsize
               for s in jax.tree.leaves(lfm2.param_shapes(cfg)))
    assert 4.665e9 < count(cfg) < 4.668e9
    assert 9.33e9 < size < 9.34e9
    whole = count(lfm2.Lfm2Config())
    assert 8.33e9 < whole < 8.35e9
    assert 8.46e9 < whole + 65536 * 2048 < 8.48e9
    shapes = lfm2.param_shapes(cfg)
    assert "head" not in shapes
    conv, attn = shapes["layers"][0]["attn"], shapes["layers"][2]["attn"]
    assert conv["in_proj"].shape == (2048, 3 * 2048)
    assert conv["conv"].shape == (3, 2048)
    assert conv["out_proj"].shape == (2048, 2048)
    assert attn["wq"].shape == attn["wo"].shape == (2048, 2048)
    assert attn["wk"].shape == attn["wv"].shape == (2048, 8 * 64)
    assert attn["q_norm"].shape == attn["k_norm"].shape == (64,)
    assert shapes["layers"][1]["mlp"]["gate"].shape == (2048, 7168)
    mlp = shapes["layers"][2]["mlp"]
    assert set(mlp) == {"router", "router_bias", "experts"}
    assert mlp["experts"]["gate"].shape == (32, 2048, 1792)
    assert mlp["router"].shape == (2048, 32)
    assert mlp["router"].dtype == mlp["router_bias"].dtype == jnp.float32
    caches = jax.eval_shape(lambda: lfm2.empty_prefill_caches(cfg, 16384))
    assert [c.shape for c in caches["conv"]] == [(1, 2, 2048)] * 11
    assert [c["k"].shape for c in caches["kv"]] == [(1, 16384, 8, 64)] * 3


def test_the_two_kinds_of_cache_and_their_bytes():
    """A conv layer's tail does not grow with the capacity or the new
    tokens; an attention layer's keys and values do."""
    tail = 2 * 128 * 4                          # float32 TINY
    entry = 2 * 2 * 16 * 4
    for capacity in (64, 1024):
        assert lfm2.cache_bytes(CFG, 3, capacity, 16) == {
            "recurrent": 5 * 3 * tail, "full": (capacity + 3 * 16) * entry}
    got = lfm2.cache_bytes(published(), 32, 16384, 128)
    assert got["recurrent"] == 11 * 32 * 2 * 2048 * 2
    assert got["full"] == 3 * (16384 + 32 * 128) * 2 * 8 * 64 * 2


# ---- the gated short convolution -------------------------------------------


def _direct(p, x):
    """The gated conv of one whole sequence x (T, d), by its definition in
    float64 on the host: v_t = sum_i w_i z_(t-2+i), z before 0 zero."""
    x = np.asarray(x, np.float64)
    b, c, u = np.split(x @ np.asarray(p["in_proj"], np.float64), 3, -1)
    z = np.concatenate([np.zeros((2, x.shape[1])), b * u])
    w = np.asarray(p["conv"], np.float64)
    v = sum(z[i:i + len(x)] * w[i] for i in range(3))
    return (c * v) @ np.asarray(p["out_proj"], np.float64)


def test_the_conv_alone_is_a_direct_convolution(params):
    """Chunks of 5 with the last one part padding, then a token at a time:
    the tail carried across chunks and rolled by each step gives the
    convolution of the whole sequence."""
    p = params["layers"][0]["attn"]
    x = jnp.asarray(np.random.RandomState(4).randn(1, 23, 128), jnp.float32)
    want = _direct(p, x[0])
    tail = jnp.zeros((1, 2, 128), jnp.float32)
    outs = []
    for pos in range(0, 15, 5):
        part = jnp.zeros((1, 5, 128)).at[:, :min(5, 13 - pos)].set(
            x[:, pos:min(pos + 5, 13)])
        y, tail = text_layers.gated_conv(p, part, tail, min(5, 13 - pos))
        outs.append(np.asarray(y[0, :min(5, 13 - pos)]))
    for t in range(13, 23):
        y, tail = text_layers.gated_conv(p, x[:, t:t + 1], tail, 1)
        outs.append(np.asarray(y[0]))
    got = np.concatenate(outs)
    assert np.abs(got - want).max() < LAYER_TOL * np.abs(want).max()
    # the tail is z's last two values; no activation anywhere
    b, _, u = np.split(np.asarray(x[0, -2:]) @ np.asarray(p["in_proj"]), 3,
                       -1)
    assert np.abs(np.asarray(tail[0]) - b * u).max() < 1e-4
    # a conv with SiLU behind it (the delta rule's) is another function
    silu = text_layers.short_conv(
        x, jnp.zeros((1, 2, 128)), p["conv"], 23)[0]
    plain = text_layers.short_conv(
        x, jnp.zeros((1, 2, 128)), p["conv"], 23, activation=None)[0]
    assert np.abs(np.asarray(jax.nn.silu(plain) - silu)).max() < 1e-6
    assert np.abs(np.asarray(plain - silu)).max() > 0.1


def test_the_delta_rules_short_conv_keeps_its_program():
    """``short_conv`` as the delta rule calls it (no activation named):
    the same primitives as a SiLU written out behind a plain conv."""
    args = (jnp.zeros((1, 8, 16)), jnp.zeros((1, 3, 16)),
            jnp.zeros((4, 16)), jnp.int32(8))
    default = str(jax.make_jaxpr(text_layers.short_conv)(*args))
    silu = str(jax.make_jaxpr(lambda *a: text_layers.short_conv(
        *a, activation=jax.nn.silu))(*args))
    plain = str(jax.make_jaxpr(lambda *a: text_layers.short_conv(
        *a, activation=None))(*args))
    assert default == silu and "logistic" in default
    assert "logistic" not in plain


# ---- the whole stack -------------------------------------------------------


@pytest.mark.parametrize("n_prompt, n_new, chunk", [
    (37, 14, 16),       # three chunks, the last padded: tails cross twice
    (32, 10, 16),       # two whole chunks of 16
    (1, 12, 16),        # a one-token prompt: the decode's tail starts at 0
], ids=["37+14", "32+10", "1+12"])
def test_prefill_then_cached_decode_is_the_full_forward(params, sizes,
                                                        n_prompt, n_new,
                                                        chunk):
    """The prompt in chunks through both kinds of cache, then
    teacher-forced tokens on two rows: every position's logits against
    one uncached pass of the reference."""
    rng = np.random.RandomState(n_prompt)
    ids = rng.randint(0, CFG.vocab_size, n_prompt + n_new)
    want = np.asarray(lfm2ref.forward(params, sizes, ids))
    assert np.abs(want).max() > 0.5
    logits, caches, _ = prefill(params, CFG, ids[:n_prompt], chunk=chunk)
    assert np.abs(np.asarray(logits[0]) - want[n_prompt - 1]).max() \
        < LOGIT_TOL
    caches = lfm2.decode_caches(CFG, caches, 2, 16)
    step = jax.jit(lambda p, t, c, n, s: lfm2.decode_step(
        p, CFG, t, c, n, s))
    for t in range(n_new):
        token = jnp.asarray([ids[n_prompt + t]] * 2, jnp.int32)
        logits, caches, _ = step(params, token, caches,
                                 jnp.int32(n_prompt), jnp.int32(t))
        for row in range(2):
            assert np.abs(np.asarray(logits[row])
                          - want[n_prompt + t]).max() < LOGIT_TOL
    # the same pass with bfloat16-rounded weights misses the limit
    logits16, _, _ = prefill(bf16(params), CFG, ids[:n_prompt], chunk=chunk)
    assert np.abs(np.asarray(logits16[0]) - want[n_prompt - 1]).max() \
        > 10 * LOGIT_TOL


def _prompt_tail_dropped(monkeypatch):
    """Every prefill chunk starts its convs from a zero tail."""
    conv = text_layers.gated_conv

    def dropped(p, x, tail, n_valid):
        if x.shape[1] > 1:
            tail = jnp.zeros_like(tail)
        return conv(p, x, tail, n_valid)

    monkeypatch.setattr(text_layers, "gated_conv", dropped)


def _decode_tail_kept(monkeypatch):
    """A decode step hands the tail it was given on, unrolled."""
    conv = text_layers.gated_conv

    def kept(p, x, tail, n_valid):
        y, new = conv(p, x, tail, n_valid)
        return y, (tail if x.shape[1] == 1 else new)

    monkeypatch.setattr(text_layers, "gated_conv", kept)


def _b_and_c_swapped(monkeypatch):
    def swapped(p, x, tail, n_valid):
        b, c, u = jnp.split(text_layers.proj(x, p["in_proj"]), 3, axis=-1)
        z, tail = text_layers.short_conv(c * u, tail, p["conv"], n_valid,
                                         activation=None)
        return text_layers.proj(b * z, p["out_proj"]), tail

    monkeypatch.setattr(text_layers, "gated_conv", swapped)


def _told_with(monkeypatch, **changes):
    told = lfm2._told
    monkeypatch.setattr(lfm2, "_told", lambda c: dict(told(c), **changes))


def _wrong_kv_head(monkeypatch):
    qkv = text_layers._queries_keys_values

    def rolled(p, x, positions, heads, kv_heads, *rest):
        q, k, v = qkv(p, x, positions, heads, kv_heads, *rest)
        return q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2)

    monkeypatch.setattr(text_layers, "_queries_keys_values", rolled)


FAULTS = {
    "prompt-tail-dropped": _prompt_tail_dropped,
    "decode-tail-kept": _decode_tail_kept,
    "b-and-c-swapped": _b_and_c_swapped,
    "no-qk-norm": lambda m: _told_with(m, qk_norm_eps=None),
    "theta-1e4": lambda m: _told_with(
        m, inv_freq=text_layers.rope_frequencies(1e4, CFG.head_dim)),
    "wrong-kv-head": _wrong_kv_head,
    "bias-ignored": lambda m: m.setattr(lfm2, "route", lambda p, c, x:
                                        text_layers.sigmoid_route(
                                            dict(p, router_bias=0.0 * p[
                                                "router_bias"]), c, x)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_what_this_stack_tells_the_shared_layers_matters(params, sizes,
                                                         fault, monkeypatch):
    """Each reading of the config that the program could get wrong moves
    the logits of a chunked prefill and a decode far past the tolerance
    (so the agreement above is not blind to it): a 37-token prompt in
    chunks of 16, then 8 tokens on one row."""
    ids = np.random.RandomState(5).randint(0, CFG.vocab_size, 45)
    want = np.asarray(lfm2ref.forward(params, sizes, ids))
    qk_norm = dict(params)
    if fault == "no-qk-norm":
        # gains of one would hide a norm left out only at unit scale:
        # give them the benchmark's seeded gains
        qk_norm = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 1.6 if path[-1].key in ("q_norm", "k_norm")
            else x, params)
        want = np.asarray(lfm2ref.forward(qk_norm, sizes, ids))
    FAULTS[fault](monkeypatch)
    logits, caches, _ = prefill(qk_norm, CFG, ids[:37], chunk=16)
    caches = lfm2.decode_caches(CFG, caches, 1, 16)
    got = [np.asarray(logits[0])]
    for t in range(8):
        logits, caches, _ = lfm2.decode_step(
            qk_norm, CFG, jnp.asarray([ids[37 + t]], jnp.int32), caches,
            jnp.int32(37), jnp.int32(t))
        got.append(np.asarray(logits[0]))
    assert np.abs(np.stack(got) - want[36:45]).max() > 100 * LOGIT_TOL


def test_the_tied_head_reads_the_embedding_in_place(params):
    """Logits are the normed hidden state times the embedding's rows; the
    program holds no transposed copy of the (V, d) table (no transpose
    in its jaxpr), and a stack with a head of its own still reads it."""
    x = jnp.asarray(np.random.RandomState(2).randn(3, 128), jnp.float32)
    got = text_layers.logits_of(params, CFG, x)
    h = text_layers.rms_norm(x, params["final_norm"], CFG.norm_eps)
    want = np.asarray(h, np.float64) @ np.asarray(params["embed"],
                                                  np.float64).T
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    jaxpr = str(jax.make_jaxpr(lambda p, x: text_layers.logits_of(
        p, CFG, x))(params, x))
    assert "transpose" not in jaxpr and "dot_general" in jaxpr
    untied = dict(params, head=jnp.asarray(params["embed"]).T * 2.0)
    assert np.abs(np.asarray(text_layers.logits_of(untied, CFG, x))
                  - 2.0 * want).max() < 1e-3


def test_the_expert_layer_has_no_shared_expert(params, sizes):
    """The layer is the held experts' part alone (the reference's sum over
    the chosen experts with no shared SwiGLU); a layer with a shared
    expert adds it."""
    layer = params["layers"][3]["mlp"]
    assert "shared" not in layer
    x = jnp.asarray(np.random.RandomState(6).randn(24, 128), jnp.float32)
    y, stats = lfm2.moe(layer, CFG, x)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lfm2ref.moe_layer(layer, sizes, x, "float32"))
    assert np.abs(np.asarray(y) - want).max() < LAYER_TOL
    assert int(stats["pairs_held"]) == 24 * 4
    chosen, weight = lfm2.route(layer, CFG, x)
    part, _, _ = text_layers.held_experts_part(
        layer, CFG, x, chosen, weight, jnp.ones((24,), bool))
    assert np.abs(np.asarray(y) - np.asarray(part)).max() == 0.0
    shared = {name: jnp.ones_like(w) * 0.01
              for name, w in layer["experts"].items()}
    with_shared = dict(layer, shared={k: v[0] for k, v in shared.items()})
    y2, _ = lfm2.moe(with_shared, CFG, x)
    assert np.abs(np.asarray(y2 - y)).max() > 1e-3


def test_the_router_normalises_with_its_guard(sizes):
    """Weights are the chosen scores over their sum + 1e-6, chosen by
    score + bias; a configuration without the guard (Solar's) divides by
    the plain sum."""
    layer = lfm2.random_params(CFG, seed=4)["layers"][4]["mlp"]
    x = jnp.asarray(np.random.RandomState(7).randn(40, 128), jnp.float32)
    chosen, weight = lfm2.route(layer, CFG, x)
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(x @ layer["router"]), np.float64)
    best = np.argsort(-(scores + np.asarray(layer["router_bias"])), -1,
                      kind="stable")[:, :4]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(best, -1))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    assert np.allclose(np.asarray(weight),
                       picked / (picked.sum(-1, keepdims=True) + 1e-6),
                       rtol=1e-6)
    # the bias decides some choices
    plain = np.argsort(-scores, -1, kind="stable")[:, :4]
    assert not np.array_equal(np.sort(plain, -1), np.sort(best, -1))
    assert getattr(solar.SolarConfig, "norm_topk_eps", 0.0) == 0.0
    ref_chosen, ref_weight = lfm2ref.route(layer, sizes, x)
    assert np.abs(np.asarray(ref_weight) - np.asarray(weight)).max() < 1e-6


def test_the_expert_shares_add_up_to_the_uncut_layer(params, sizes):
    """Eight chips of two experts each: the parts their held experts
    give (no shared expert to count once) are the whole layer of the
    uncut reference."""
    layer = params["layers"][5]["mlp"]
    x = jnp.asarray(np.random.RandomState(6).randn(24, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lfm2ref.moe_layer(layer, sizes, x, "float32"))
    total = np.zeros_like(want)
    pairs_held = 0
    for first in range(0, 16, 2):
        cfg = dataclasses.replace(CFG, experts_held=(first, first + 2))
        share = dict(layer, experts={
            name: mat[first:first + 2]
            for name, mat in layer["experts"].items()})
        y, stats = lfm2.moe(share, cfg, x)
        total += np.asarray(y)
        pairs_held += int(stats["pairs_held"])
        assert np.abs(np.asarray(y) - want).max() > 100 * LAYER_TOL
    assert np.abs(total - want).max() < LAYER_TOL
    assert pairs_held == 24 * CFG.num_experts_per_tok


# ---- the kernel entries at a head of 64 ------------------------------------


def dense(q, k, v, offset, scale):
    g = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("blhd,bshd->bhls", q, kk) * scale
    row = offset + jnp.arange(q.shape[1])[:, None]
    seen = jnp.arange(k.shape[1])[None] <= row
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhls,bshd->blhd", weights, vv)


@pytest.mark.parametrize("offset", [0, 24])
def test_the_grouped_prefill_at_a_head_of_64(offset):
    """The cell's head (64, zero-padded to the 128-lane tile) and group
    (4 query heads a key-value head): the grouped causal sweep against
    the dense masked form, NaN past the written cache."""
    rng = np.random.RandomState(offset)
    q = jnp.asarray(rng.randn(1, 16, 8, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 64, 2, 64), jnp.float32
                        ).at[:, offset + 16:].set(jnp.nan) for _ in range(2))
    got = kernel.causal_flash_attention(q, k, v, jnp.int32(offset),
                                        scale=0.125, block_q=32, block_kv=16)
    want = dense(q, jnp.nan_to_num(k), jnp.nan_to_num(v), offset, 0.125)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < KERNEL_TOL


def test_the_shared_prompt_sweep_at_a_head_of_64():
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(3, 8, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(48, 2, 64), jnp.float32
                        ).at[33:].set(jnp.nan) for _ in range(2))
    o, lse = kernel.shared_prompt_attention(q, k, v, jnp.int32(33),
                                            scale=0.125, block_kv=16)
    kk, vv = (jnp.repeat(jnp.nan_to_num(z), 4, axis=1) for z in (k, v))
    scores = jnp.einsum("rhd,shd->rhs", q, kk) * 0.125
    scores = jnp.where(jnp.arange(48) < 33, scores, -jnp.inf)
    want = jnp.einsum("rhs,shd->rhd", jax.nn.softmax(scores, -1), vv)
    assert np.abs(np.asarray(o) - np.asarray(want)).max() < KERNEL_TOL
    assert np.abs(np.asarray(lse) - np.asarray(
        jax.nn.logsumexp(scores, -1))).max() < KERNEL_TOL


@pytest.mark.parametrize("entry", ["causal_flash_attention",
                                   "shared_prompt_attention"])
def test_both_kernel_entries_cross_lower_for_tpu_at_the_cells_shapes(entry):
    """A 2048-token chunk of 32 heads of 64 over 8 key-value heads against
    16,384 slots with a traced offset, and the decode's 32 rows x 32 heads
    behind a traced prompt length: one Mosaic call each under its own
    operation name. The head of 64 reaches the kernel zero-padded to the
    128-lane tile (a (1, 16384, 8, 128) copy of the keys and of the
    values): half of each tile's work is padding."""
    from unittest import mock

    from chiaswarm_tpu.ops.attention import attention, shared_prompt_attention

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if entry == "causal_flash_attention":
        def fn(q, k, v, q_offset):
            return attention(q, k, v, scale=0.125, causal=True,
                             q_offset=q_offset)

        shapes = (spec(1, 2048, 32, 64), spec(1, 16384, 8, 64),
                  spec(1, 16384, 8, 64), scalar)
    else:
        def fn(q, k, v, n):
            return shared_prompt_attention(q, k, v, n, scale=0.125)

        shapes = (spec(32, 32, 64), spec(16384, 8, 64), spec(16384, 8, 64),
                  scalar)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert entry in text
    assert "1x16384x8x128xbf16" in text and "1x16384x1024xbf16" in text


# ---- the counts ------------------------------------------------------------


def test_job_counts_are_a_brute_force_count():
    """Pairs a head scores, by phase, over the one attention layer; its
    prefill grid steps and decode key blocks; the conv layers' tail bytes
    by phase; four expert layers."""
    p, rows, new, chunk, capacity = 37, 2, 16, 16, 64
    counts = lfm2.job_counts(CFG, p, rows, new, chunk, capacity)
    steps = new - 1
    assert counts["attention_pairs"] == (
        sum(t + 1 for t in range(p)),
        rows * sum(p + s + 1 for s in range(steps)))
    # 2 key-value heads of 4 query heads' rows: chunk i (64-row blocks
    # against 16-key ones) steps i whole pairs and one across the diagonal
    assert counts["block_steps"] == {"whole": 2 * (0 + 1 + 2),
                                     "diagonal": 2 * 3, "dead": 0}
    assert counts["decode_key_blocks"] == (steps, steps)
    tail = 2 * 128 * 4
    assert counts["recurrent_state_bytes"] == {
        "prefill": 5 * 3 * 2 * tail, "decode": 5 * steps * rows * 2 * tail}
    assert counts["expert_layers"] == 4
    assert "kda_blocks" not in counts and "window_pairs" not in counts
    # the cell's job: 11 conv layers, 8 chunks, 32 rows x 127 steps
    big = lfm2.job_counts(published(), 16384, 32, 128, 2048, 16384)
    one = 2 * 2048 * 2
    assert big["recurrent_state_bytes"] == {
        "prefill": 11 * 8 * 2 * one, "decode": 11 * 127 * 32 * 2 * one}
    assert big["expert_layers"] == 12


# ---- the stack behind the pipeline, the registry and the worker ------------


def words(ids):
    from chiaswarm_tpu.pipelines.text import word_vocab

    vocab = {i: w for w, i in word_vocab(CFG.vocab_size).items()}
    return " ".join(vocab[int(i)] for i in ids)


PROMPT = words(np.random.RandomState(0).randint(0, 96, 19))


def test_the_stack_is_found_by_the_name_its_configuration_gives():
    assert text_stacks.get(CFG.stack) is lfm2
    assert "lfm2" in text_stacks.NAMES
    for needed in ("param_shapes", "random_params", "param_bytes",
                   "empty_prefill_caches", "prefill_chunk",
                   "decode_caches", "decode_step", "empty_stats",
                   "cache_bytes", "job_counts"):
        assert callable(getattr(lfm2, needed)), needed


def test_token_logprobs_of_the_pipeline_are_the_references():
    """The served log-probabilities of the sampled tokens against the
    reference's full forward over prompt + those tokens (float32 both:
    rounding, 1e-4 with room; a wrong token would be off by whole
    nats)."""
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    pipe = TextPipeline(TextComponents.random(lfm2.TINY, seed=2),
                        prefill_chunk=16, max_context=64)
    assert pipe.c.stack is lfm2
    assert pipe.c.model_name == "random/lfm2_tiny"
    out = pipe(PROMPT, seed=5, max_new_tokens=11, num_return_sequences=2,
               logprobs=True)
    prompt_ids = pipe.tokenize(PROMPT)
    sizes = sizes_of(pipe.c.config)
    for seq in out["sequences"]:
        new = pipe.c.tokenizer.tokenize(seq["text"])
        assert len(new) == len(seq["token_logprobs"]) == 11
        logits = np.asarray(lfm2ref.forward(
            pipe.c.params, sizes, np.concatenate([prompt_ids, new])),
            np.float64)[len(prompt_ids) - 1:-1]
        norm = np.log(np.exp(logits).sum(-1))
        want = logits[np.arange(11), new] - norm
        assert np.abs(want - np.asarray(seq["token_logprobs"])).max() < 1e-4


def test_an_unmodified_worker_settles_a_txt2txt_job_of_the_fifth_stack():
    """Polled, run and settled through the worker's normal path; the
    catalog entry names the stack, and the counter families moved by
    what this stack's programs returned and its host counts: the
    recurrent-state bytes (the conv tails) among them, with no delta-rule
    sub-blocks."""
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.obs.metrics import REGISTRY

    registry = ModelRegistry(
        catalog=[{"name": "tiny/lfm2", "stack": "lfm2",
                  "prefill_chunk": 16, "max_context": 64}],
        allow_random=True)
    families = ("chiaswarm_text_tokens_total",
                "chiaswarm_moe_layer_steps_total",
                "chiaswarm_text_prefill_block_steps_total",
                "chiaswarm_text_decode_key_blocks_total",
                "chiaswarm_text_attention_pairs_total",
                "chiaswarm_text_kda_blocks_total",
                "chiaswarm_text_recurrent_state_bytes_total",
                "chiaswarm_text_cache_bytes")

    def counters():
        snap = REGISTRY.snapshot()
        return {name: dict(snap[name]["values"]) for name in families}

    async def scenario():
        hive = MiniHive(lease_s=120.0, delay_s=0.0)
        uri = await hive.start()
        worker = Worker(
            settings=Settings(
                hive_uri=uri, hive_token="t", worker_name="text",
                install_signal_handlers=False, poll_busy_s=0.02,
                poll_idle_s=0.02, drain_timeout_s=30.0),
            registry=registry, pool=ChipPool(n_slots=1))
        task = asyncio.create_task(worker.run())
        try:
            hive.submit({"id": "hive-lfm2", "workflow": "txt2txt",
                         "model_name": "tiny/lfm2", "prompt": PROMPT,
                         "seed": 9, "max_new_tokens": 12,
                         "num_return_sequences": 2, "logprobs": True,
                         "content_type": "application/json"})
            await hive.wait_for_results(1, timeout=300)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=60)
            await hive.stop()
        return hive.results[0]

    before = counters()
    result = asyncio.run(scenario())
    after = counters()
    assert "error" not in result["pipeline_config"], result
    payload = json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))
    assert len(payload["sequences"]) == 2
    assert all(len(s["token_logprobs"]) == 12 for s in payload["sequences"])
    assert registry.text_pipeline("tiny/lfm2").c.stack is lfm2

    def moved(family, key):
        return after[family].get(key, 0) - before[family].get(key, 0)

    want = lfm2.job_counts(CFG, 19, 2, 16, 16, 64)
    assert moved("chiaswarm_text_tokens_total", "prefill") == 19
    assert moved("chiaswarm_moe_layer_steps_total", "") == 15 * 4
    seen = "chiaswarm_text_attention_pairs_total"
    assert (moved(seen, "prefill"), moved(seen, "decode")) \
        == want["attention_pairs"]
    steps = "chiaswarm_text_prefill_block_steps_total"
    assert {kind: moved(steps, kind) for kind in want["block_steps"]} \
        == want["block_steps"]
    kda = "chiaswarm_text_kda_blocks_total"
    assert (moved(kda, "pairwise"), moved(kda, "product")) == (0, 0)
    state = "chiaswarm_text_recurrent_state_bytes_total"
    assert {phase: moved(state, phase) for phase in ("prefill", "decode")} \
        == want["recurrent_state_bytes"]
    assert want["recurrent_state_bytes"]["decode"] > 0
    bytes_ = after["chiaswarm_text_cache_bytes"]
    assert (bytes_["recurrent"], bytes_["full"]) == (
        5 * 2 * 2 * 128 * 4, (64 + 2 * 16) * 2 * 2 * 16 * 4)


def test_the_smoke_job_of_the_fifth_stack_settles():
    """``python -m chiaswarm_tpu.node.smoke --workflow txt2txt_lfm2``:
    the hard-coded job through the real dispatch path, the stack named by
    the smoke registry's catalog entry."""
    from chiaswarm_tpu.node.smoke import run_smoke

    result = run_smoke("txt2txt_lfm2")
    assert "error" not in result["pipeline_config"], result
    payload = json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))
    assert len(payload["sequences"]) == 2
    assert all(len(s["token_logprobs"]) == 4 for s in payload["sequences"])
