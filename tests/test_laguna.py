"""The Laguna-XS.2-class decoder (models/laguna.py) against the tests'
plain float32 reference (tests/laguna_reference.py) on seeded weights at
the tiny size: hidden 64, 1 dense + 5 expert layers, two full layers of
6 heads and four sliding ones of 4 over 2 key-value heads of 16, a
window of 8 (shorter than the chunk of 16, the prompts and the new
tokens), half-rotated YaRN with a kept, a blended and a divided
frequency pair in the full layers and plain rope in the sliding ones, 16
softmax-routed experts of which 4 are held, float32 weights.

Tolerances. Program and reference compute the same function in float32
in another order (chunks, a window's local buffer, grouped heads, a
shared prompt joined to a suffix, grouped experts), so they differ by
rounding: logits of magnitude ~3 agree to a few 1e-6. The limits sit a
decade above that, and the tests show that the same computation with
bfloat16-rounded operands (relative step 2^-8) misses its limit by a
wide margin: a lower precision cannot hide inside them.
"""

import asyncio
import base64
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models import laguna, text_layers, text_stacks
from chiaswarm_tpu.ops import causal_flash_attention as kernel
from chiaswarm_tpu.ops.attention import attention, shared_prompt_attention

import laguna_reference as ref

CFG = laguna.TINY
LOGIT_TOL = 5e-5      # |logit| ~ 3: a few float32 roundings, ~10x room
LAYER_TOL = 2e-5      # one layer's output, magnitude ~1
KERNEL_TOL = 5e-6     # one softmax read-out of unit values


def bf16(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


@pytest.fixture(scope="module")
def params():
    return laguna.random_params(CFG, seed=3)


@pytest.fixture(scope="module")
def sizes():
    return ref.sizes_of(CFG)


def prefill(params, cfg, ids, chunk, capacity=64):
    caches = laguna.empty_prefill_caches(cfg, capacity)
    fn = jax.jit(lambda p, i, c, pos, n: laguna.prefill_chunk(
        p, cfg, i, c, pos, n))
    for pos in range(0, len(ids), chunk):
        part = np.zeros((1, chunk), np.int32)
        n = min(chunk, len(ids) - pos)
        part[0, :n] = ids[pos:pos + n]
        logits, caches, stats = fn(params, jnp.asarray(part), caches,
                                   jnp.int32(pos), jnp.int32(n))
    return logits, caches, stats


# ---- the preset and the layout ---------------------------------------------


def test_the_tiny_preset_holds_every_case_the_stack_has():
    assert CFG.layer_types == (laguna.FULL,) + (laguna.SLIDING,) * 3 \
        + (laguna.FULL, laguna.SLIDING)
    assert CFG.num_attention_heads_per_layer == (6, 4, 4, 4, 6, 4)
    assert CFG.num_key_value_heads == 2
    assert CFG.mlp_layer_types == ("dense",) + ("sparse",) * 5
    assert (CFG.num_experts, CFG.experts_held) == (16, (0, 4))
    assert CFG.sliding_window == 8
    full = CFG.rope_parameters.full_attention
    # 8 of a head's 16 values rotated: 4 pairs, pairs 0 and 1 kept, pair
    # 2 blended by half, pair 3 divided by the factor
    assert text_layers.yarn_band(8, full.rope_theta, 256, full.beta_fast,
                                 full.beta_slow) == (1, 3)
    freq, amplitude = laguna.rotary(CFG, laguna.FULL)
    plain = 10000.0 ** (-np.arange(4) / 4)
    assert np.allclose(freq, plain * [1, 1, (1 + 1 / 4) / 2, 1 / 4],
                       rtol=1e-6)
    assert amplitude == pytest.approx(0.1 * math.log(4.0) + 1, abs=1e-4)
    freq, amplitude = laguna.rotary(CFG, laguna.SLIDING)
    assert np.allclose(freq, 100.0 ** (-np.arange(8) / 8), rtol=1e-6)
    assert amplitude == 1.0
    with pytest.raises(ValueError, match="per-layer lists"):
        dataclasses.replace(CFG, num_hidden_layers=5)


def test_yarn_for_the_published_keys():
    """The full layers of the published config: 64 rotated values at
    theta 500,000, factor 64 over 4,096 positions: low, high = 5, 16 by
    hand, the slow pairs divided by 64, cos and sin times 0.1 ln 64 + 1;
    the sliding layers plain at theta 10,000 over all 128."""
    cfg = laguna.LagunaConfig()
    r = cfg.rope_parameters.full_attention
    low = 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(5e5))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(low), math.ceil(high)) == (5, 16)
    assert text_layers.yarn_band(64, r.rope_theta, 4096, r.beta_fast,
                                 r.beta_slow) == (5, 16)
    freq, amplitude = laguna.rotary(cfg, laguna.FULL)
    plain = 5e5 ** (-np.arange(32) / 32)
    assert freq.shape == (32,)
    assert np.allclose(freq[:6], plain[:6], rtol=1e-6)
    assert np.allclose(freq[16:], plain[16:] / 64, rtol=1e-6)
    assert np.all((freq[6:16] < plain[6:16])
                  & (freq[6:16] > plain[6:16] / 64))
    assert amplitude == pytest.approx(0.1 * math.log(64.0) + 1, abs=1e-5)
    freq, amplitude = laguna.rotary(cfg, laguna.SLIDING)
    assert freq.shape == (64,) and amplitude == 1.0
    assert np.allclose(freq, 1e4 ** (-np.arange(64) / 64), rtol=1e-6)


def test_the_layout_is_the_published_one_at_the_cut():
    """The benchmark's cut: layers 0-6 (F S S S F S S), every expert and
    the whole vocabulary: 5.564 B parameters, 11.13 GB in bfloat16; the
    whole model 33.4 B."""
    whole = laguna.LagunaConfig()
    cfg = dataclasses.replace(
        whole, num_hidden_layers=7, layer_types=whole.layer_types[:7],
        mlp_layer_types=whole.mlp_layer_types[:7],
        num_attention_heads_per_layer=whole.num_attention_heads_per_layer[:7])

    def count(c):
        return sum(int(np.prod(s.shape))
                   for s in jax.tree.leaves(laguna.param_shapes(c)))

    assert 5.56e9 < count(cfg) < 5.57e9
    assert 33.0e9 < count(whole) < 33.8e9
    shapes = laguna.param_shapes(cfg)
    full, sliding = shapes["layers"][4]["attn"], shapes["layers"][5]["attn"]
    assert full["wq"].shape == (2048, 48 * 128)
    assert sliding["wq"].shape == (2048, 64 * 128)
    assert full["wk"].shape == sliding["wv"].shape == (2048, 8 * 128)
    assert (full["wg"].shape, sliding["wg"].shape) == ((2048, 48),
                                                       (2048, 64))
    assert sliding["wo"].shape == (64 * 128, 2048)
    mlp = shapes["layers"][1]["mlp"]
    assert mlp["experts"]["gate"].shape == (256, 2048, 512)
    assert mlp["router"].shape == (2048, 256)
    assert mlp["router"].dtype == jnp.float32
    assert mlp["shared"]["down"].shape == (512, 2048)
    assert shapes["layers"][0]["mlp"]["gate"].shape == (2048, 8192)
    assert shapes["head"].shape == (2048, 100352)
    caches = jax.eval_shape(lambda: laguna.empty_prefill_caches(cfg, 16384))
    assert [c["k"].shape[1] for c in caches["kv"]] \
        == [16384, 512, 512, 512, 16384, 512, 512]
    assert all(c["v"].shape[2:] == (8, 128) for c in caches["kv"])


def test_a_sliding_layers_cache_does_not_grow_with_the_capacity():
    entry = 2 * 2 * 16 * 4          # k and v, 2 heads of 16, float32
    for capacity in (64, 1024):
        got = laguna.cache_bytes(CFG, 3, capacity, 16)
        assert got == {"full": 2 * (capacity + 3 * 16) * entry,
                       "window": 4 * (8 + 3 * 16) * entry}
    big = dataclasses.replace(laguna.LagunaConfig(), dtype="bfloat16")
    a = laguna.cache_bytes(big, 32, 16384, 128)
    b = laguna.cache_bytes(big, 32, 262144, 128)
    assert a["window"] == b["window"] and b["full"] > 10 * a["full"]


# ---- the whole stack -------------------------------------------------------


@pytest.mark.parametrize("n_prompt, n_new, chunk", [
    (37, 14, 16),       # three chunks, the last padded; more new than 8
    (16, 10, 16),       # one whole chunk
    (5, 12, 16),        # a prompt shorter than the window
], ids=["37+14", "16+10", "5+12"])
def test_prefill_then_cached_decode_is_the_full_forward(params, sizes,
                                                        n_prompt, n_new,
                                                        chunk):
    """The prompt in chunks of 16 through both kinds of cache, then
    teacher-forced tokens on two rows, more of them than the window
    holds: every position's logits against one uncached pass of the
    reference."""
    rng = np.random.RandomState(n_prompt)
    ids = rng.randint(0, CFG.vocab_size, n_prompt + n_new)
    want = np.asarray(ref.forward(params, sizes, ids))
    assert np.abs(want).max() > 0.5
    logits, caches, _ = prefill(params, CFG, ids[:n_prompt], chunk=chunk)
    assert np.abs(np.asarray(logits[0]) - want[n_prompt - 1]).max() \
        < LOGIT_TOL
    caches = laguna.decode_caches(CFG, caches, 2, 16)
    assert [c["k"].shape[1] for c in caches["prompt"]] \
        == [64, 8, 8, 8, 64, 8]
    step = jax.jit(lambda p, t, c, n, s: laguna.decode_step(
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


def test_a_whole_chunk_and_a_padded_one_give_the_same_logits(params):
    """40 tokens in chunks of 8 against the same tokens in chunks of 16
    whose last one is half padding: the padded entries lie past every
    query of a full layer and never enter a sliding layer's window, so
    the logits and both kinds of cache are the same."""
    ids = np.random.RandomState(1).randint(0, CFG.vocab_size, 40)
    la, ca, _ = prefill(params, CFG, ids, chunk=8)
    lb, cb, _ = prefill(params, CFG, ids, chunk=16)
    assert np.abs(np.asarray(la) - np.asarray(lb)).max() < LOGIT_TOL
    for i, (a, b) in enumerate(zip(ca["kv"], cb["kv"])):
        used = 8 if CFG.window(i) else 40
        for name in ("k", "v"):
            assert np.abs(np.asarray(a[name][:, :used])
                          - np.asarray(b[name][:, :used])).max() < LAYER_TOL


def test_a_sliding_layers_cache_holds_the_last_window_of_the_prompt(params):
    """After 37 tokens the sliding layers hold positions 29..36 in slots
    0..7, whatever the chunking; a full layer holds all 37 where they
    lie."""
    ids = np.random.RandomState(2).randint(0, CFG.vocab_size, 37)
    _, by16, _ = prefill(params, CFG, ids, chunk=16)
    _, whole, _ = prefill(params, CFG, ids, chunk=64)
    for i in range(CFG.num_hidden_layers):
        if not CFG.window(i):
            continue
        # the 64-token chunk's local buffer held every key: its last 8
        for name in ("k", "v"):
            assert np.abs(np.asarray(by16["kv"][i][name])
                          - np.asarray(whole["kv"][i][name])).max() \
                < LAYER_TOL
    # a prompt shorter than the window lies in slots 0..4 (what the
    # padding wrote past them no query ever sees: the decode test's
    # 5-token prompt)
    _, short, _ = prefill(params, CFG, ids[:5], chunk=16)
    _, exact, _ = prefill(params, CFG, ids[:5], chunk=5)
    assert np.abs(np.asarray(short["kv"][1]["k"][:, :5])
                  - np.asarray(exact["kv"][1]["k"][:, :5])).max() < LAYER_TOL
    assert np.abs(np.asarray(short["kv"][1]["k"][:, :5])).min() > 0


@pytest.mark.parametrize("fault", [
    "no-window", "all-rotated", "wrong-kv-head", "no-gate",
    "no-scaling", "no-yarn-amplitude"])
def test_what_this_stack_tells_the_shared_core_matters(params, sizes, fault,
                                                       monkeypatch):
    """Each reading of the config that the program could get wrong moves
    the logits far past the tolerance (so the agreement above is not
    blind to it)."""
    ids = np.random.RandomState(5).randint(0, CFG.vocab_size, 30)
    want = np.asarray(ref.forward(params, sizes, ids))
    told = laguna._told

    def retold(cfg, layer):
        t = told(cfg, layer)
        if fault == "no-window" and layer == 2:
            t["window"] = 30         # a window no key of the prompt leaves
        if fault == "all-rotated" and layer == 4:
            r = cfg.rope_parameters.full_attention
            t["inv_freq"] = jnp.asarray(text_layers.yarn_frequencies(
                cfg.head_dim, r.rope_theta, r.factor,
                r.original_max_position_embeddings, r.beta_fast,
                r.beta_slow))
        if fault == "no-yarn-amplitude" and layer == 4:
            t["rope_amplitude"] = 1.0
        return t

    monkeypatch.setattr(laguna, "_told", retold)
    if fault == "wrong-kv-head":
        qkv = text_layers._queries_keys_values

        def rolled(p, x, positions, heads, kv_heads, *rest):
            q, k, v = qkv(p, x, positions, heads, kv_heads, *rest)
            return q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2)

        monkeypatch.setattr(text_layers, "_queries_keys_values", rolled)
    if fault == "no-gate":
        monkeypatch.setattr(
            laguna, "_out", lambda p, x, o: text_layers.proj(
                o.reshape(*x.shape[:2], -1), p["wo"]))
    if fault == "no-scaling":
        route = laguna.route

        def unscaled(p, cfg, x):
            chosen, weight = route(p, cfg, x)
            return chosen, weight / cfg.moe_routed_scaling_factor

        monkeypatch.setattr(laguna, "route", unscaled)
    logits, _, _ = prefill(params, CFG, ids, chunk=64)
    assert np.abs(np.asarray(logits[0]) - want[-1]).max() > 100 * LOGIT_TOL


# ---- the kernel's new entries, in interpret mode ---------------------------


def dense(q, k, v, offset, window, scale):
    """The dense masked einsum: q (1, L, H, D) at positions offset + l
    over k, v (1, S, Hk, D), head j reading key-value head j // (H / Hk),
    causal and, with a window, its last ``window`` keys."""
    g = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("blhd,bshd->bhls", q, kk) * scale
    row = offset + jnp.arange(q.shape[1])[:, None]
    col = jnp.arange(k.shape[1])[None]
    seen = col <= row
    if window:
        seen &= col > row - window
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhls,bshd->blhd", weights, vv)


def operands(seed, l, h, hk, s, written, d=16):
    """q, k, v with NaN in every key and value slot past ``written``."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, l, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(1, s, hk, d), jnp.float32)
    v = jnp.asarray(rng.randn(1, s, hk, d), jnp.float32)
    return q, k.at[:, written:].set(jnp.nan), v.at[:, written:].set(jnp.nan)


WINDOW_CASES = {
    # (queries, heads, kv heads, slots, offset, window, block_q, block_kv)
    "window = key block": (16, 8, 2, 40, 8, 8, 16, 8),
    "window < key block": (16, 8, 2, 48, 16, 5, 16, 16),
    "window > key block": (16, 4, 2, 48, 24, 20, 8, 8),
    "a query block straddles the window's edge": (16, 8, 2, 40, 8, 8, 24, 8),
    "the first chunk, nothing behind it": (16, 4, 2, 24, 0, 8, 8, 8),
    "one head a key-value head": (16, 2, 2, 40, 8, 8, 8, 8),
    "rows that do not fill their block": (13, 6, 2, 64, 16, 7, 16, 8),
    "the default blocks": (24, 6, 2, 64, 24, 16, None, None),
}


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_the_windowed_sweep_is_the_dense_masked_form(case):
    l, h, hk, s, offset, window, block_q, block_kv = WINDOW_CASES[case]
    q, k, v = operands(len(case), l, h, hk, s, offset + l)
    got = kernel.window_flash_attention(
        q, k, v, jnp.int32(offset), window=window, scale=0.25,
        block_q=block_q, block_kv=block_kv)
    want = dense(q, jnp.nan_to_num(k), jnp.nan_to_num(v), offset, window,
                 0.25)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < KERNEL_TOL
    # without the window the same call reads further back and differs
    if offset + l > window + 1:
        loose = dense(q, jnp.nan_to_num(k), jnp.nan_to_num(v), offset, None,
                      0.25)
        assert np.abs(np.asarray(got) - np.asarray(loose)).max() \
            > 1000 * KERNEL_TOL


GROUPED_CASES = {
    # (queries, heads, kv heads, slots, offset, block_q, block_kv)
    "six heads a key-value head": (16, 12, 2, 48, 16, 16, 8),
    "the first chunk": (16, 6, 2, 48, 0, 24, 16),
    "a block that ends inside a position": (16, 6, 2, 48, 32, 40, 8),
    "rows that do not fill their block": (13, 6, 2, 64, 16, 16, 8),
    "the default blocks": (24, 8, 2, 64, 40, None, None),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
def test_the_grouped_prefill_is_the_dense_masked_form(case):
    """``attention(causal=True)`` with fewer key-value heads than query
    heads: a group's heads go through the sweep as rows of one position
    and come back in the published head order."""
    l, h, hk, s, offset, block_q, block_kv = GROUPED_CASES[case]
    q, k, v = operands(len(case), l, h, hk, s, offset + l)
    got = kernel.causal_flash_attention(
        q, k, v, jnp.int32(offset), scale=0.25, block_q=block_q,
        block_kv=block_kv)
    want = dense(q, jnp.nan_to_num(k), jnp.nan_to_num(v), offset, None, 0.25)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < KERNEL_TOL
    # a head that read its neighbour's key-value head would differ
    wrong = dense(q, jnp.nan_to_num(jnp.roll(k, 1, 2)),
                  jnp.nan_to_num(jnp.roll(v, 1, 2)), offset, None, 0.25)
    assert np.abs(np.asarray(got) - np.asarray(wrong)).max() \
        > 1000 * KERNEL_TOL


def test_the_door_takes_groups_and_a_window_and_refuses_what_it_cannot():
    q, k, v = operands(1, 16, 8, 2, 40, 24)
    kw = dict(scale=0.25, causal=True, q_offset=jnp.int32(8))
    got = attention(q, k, v, window=8, **kw)
    want = dense(q, jnp.nan_to_num(k), jnp.nan_to_num(v), 8, 8, 0.25)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < KERNEL_TOL
    got = attention(q, k, v, **kw)
    want = dense(q, jnp.nan_to_num(k), jnp.nan_to_num(v), 8, None, 0.25)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < KERNEL_TOL
    with pytest.raises(ValueError, match="causal=True"):
        attention(q, k, v, window=8)
    shared = (q[..., :8], k[:, :, 0, :8])
    with pytest.raises(ValueError, match="shared key"):
        attention(q, k, v, shared_key=shared, window=8, **kw)
    with pytest.raises(ValueError, match="shared key"):
        attention(q, k, v, shared_key=shared, **kw)
    with pytest.raises(ValueError, match="key-value heads"):
        attention(q[:, :, :7], k, v, **kw)


@pytest.mark.parametrize("rows, heads, kv_heads, slots, n_keys, block_kv", [
    (3, 6, 2, 40, 17, 8), (4, 8, 2, 64, 64, 16), (2, 4, 2, 32, 1, None),
    (5, 12, 2, 48, 33, None)],
    ids=["17-of-40", "a-full-cache", "one-key", "six-heads-a-group"])
def test_the_shared_prompt_sweep_is_the_dense_softmax_with_its_lse(
        rows, heads, kv_heads, slots, n_keys, block_kv):
    """Every row's heads behind the first ``n_keys`` of the prompt's keys
    and values, NaN past them: the read-out and each row's log-sum-exp
    against the dense masked softmax."""
    rng = np.random.RandomState(rows)
    q = jnp.asarray(rng.randn(rows, heads, 16), jnp.float32)
    k = jnp.asarray(rng.randn(slots, kv_heads, 16),
                    jnp.float32).at[n_keys:].set(jnp.nan)
    v = jnp.asarray(rng.randn(slots, kv_heads, 16),
                    jnp.float32).at[n_keys:].set(jnp.nan)
    if block_kv is None:
        o, lse = shared_prompt_attention(q, k, v, jnp.int32(n_keys),
                                         scale=0.25)
    else:
        o, lse = kernel.shared_prompt_attention(
            q, k, v, jnp.int32(n_keys), scale=0.25, block_kv=block_kv)
    g = heads // kv_heads
    kk = jnp.repeat(jnp.nan_to_num(k), g, axis=1)
    vv = jnp.repeat(jnp.nan_to_num(v), g, axis=1)
    scores = jnp.einsum("rhd,shd->rhs", q, kk) * 0.25
    scores = jnp.where(jnp.arange(slots) < n_keys, scores, -jnp.inf)
    want = jnp.einsum("rhs,shd->rhd", jax.nn.softmax(scores, -1), vv)
    assert o.dtype == lse.dtype == jnp.float32
    assert np.abs(np.asarray(o) - np.asarray(want)).max() < KERNEL_TOL
    assert np.abs(np.asarray(lse) - np.asarray(
        jax.nn.logsumexp(scores, -1))).max() < KERNEL_TOL


@pytest.mark.parametrize("rows, g, offset, keys, window", [
    (128, 8, 8, 24, 8), (96, 6, 0, 40, 5), (2048 * 8, 8, 512, 2560, 512),
    (2048 * 8, 8, 0, 2560, 512), (40, 4, 3, 64, 16)])
def test_stepped_pairs_are_the_blocks_a_brute_force_count_finds(
        rows, g, offset, keys, window):
    """The host's count of what the windowed kernel steps: every (query
    block, key block) that holds a visible pair, found pair by pair."""
    block_q = kernel._clamp_block(rows, kernel._BLOCK_Q)
    block_kv = kernel.window_key_block(keys)
    row = offset * g + np.arange(-(-rows // block_q) * block_q)
    col = np.arange(keys)
    seen = (col[None] * g <= row[:, None]) \
        & ((col[None] + window) * g > row[:, None])
    blocks = seen.reshape(-1, block_q, keys)[
        :, :, :keys // block_kv * block_kv].reshape(
            -1, block_q, keys // block_kv, block_kv).any((1, 3)).sum()
    got = kernel.stepped_pairs(rows, g, offset, keys, window)
    assert got == blocks * block_q * block_kv
    assert got >= seen[:rows].sum()


def test_job_counts_are_a_brute_force_count_of_pairs():
    """Pairs a head scores, by phase: the causal half in a full layer,
    the window in a sliding one; the window's pairs visible and scored;
    key blocks of both kinds of prefill and of the full layers' decode."""
    p, rows, new, chunk, capacity = 37, 2, 16, 16, 64
    counts = laguna.job_counts(CFG, p, rows, new, chunk, capacity)
    w, steps = CFG.sliding_window, new - 1
    full_prefill = p * (p + 1) // 2
    seen_prefill = sum(min(t + 1, w) for t in range(p))
    full_decode = rows * sum(p + s + 1 for s in range(steps))
    seen_decode = rows * sum(min(p + s + 1, w) for s in range(steps))
    assert counts["attention_pairs"] == (
        2 * full_prefill + 4 * seen_prefill,
        2 * full_decode + 4 * seen_decode)
    assert counts["window_pairs"]["visible"] \
        == 4 * (seen_prefill + seen_decode)
    # three chunks of 16 x 2 heads a group = 32 rows against the 24 slots
    # of the local buffer, one block each way: every chunk steps it (over
    # 2 for a head); the decode scores the window's 8 and the suffix's
    # 16 slots a step
    scored = 4 * (3 * 32 * 24 // 2 + rows * steps * (w + new))
    assert counts["window_pairs"]["scored"] == scored
    assert counts["expert_layers"] == 5
    # full layers: chunks end at 16, 32, 48 of 64 slots in blocks of 16;
    # sliding layers: their 24-slot buffer is one block
    assert counts["key_blocks"] == (2 * (1 + 2 + 3) + 4 * 3,
                                    2 * 3 * 4 + 4 * 3)
    assert counts["decode_key_blocks"] == (2 * steps * 1, 2 * steps * 1)
    # the cell's job: 16,384 tokens, 32 rows x 128 new, layers 0-6
    big = laguna.LagunaConfig()
    big = dataclasses.replace(
        big, num_hidden_layers=7, layer_types=big.layer_types[:7],
        mlp_layer_types=big.mlp_layer_types[:7],
        num_attention_heads_per_layer=big.num_attention_heads_per_layer[:7])
    counts = laguna.job_counts(big, 16384, 32, 128, 2048, 16384)
    seen = 5 * (512 * 513 // 2 + (16384 - 512) * 512)
    assert counts["attention_pairs"][0] == 2 * 16384 * 16385 // 2 + seen
    assert counts["window_pairs"]["visible"] \
        == seen + 5 * 32 * 127 * 512
    ratio = counts["window_pairs"]["scored"] \
        / counts["window_pairs"]["visible"]
    assert 1.0 <= ratio < 8.0
    assert counts["expert_layers"] == 6


def _shapes(jaxpr):
    """Shapes of every value a jaxpr computes, sub-jaxprs included, a
    Pallas kernel's body (its blocks live in VMEM) left out."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(getattr(var.aval, "shape", ()))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


def test_no_scores_over_the_capacity_reach_memory_in_the_decode(params):
    """No array of rows x heads x capacity scores in ``decode_step``
    outside the kernel: a full layer's scores over the prompt stay in
    VMEM, a sliding layer scores its window and the suffix only."""
    rows, capacity, max_new = 3, 40, 16      # 40 and 56: no width's size
    caches = laguna.decode_caches(
        CFG, laguna.empty_prefill_caches(CFG, capacity), rows, max_new)
    program = jax.make_jaxpr(lambda p, c: laguna.decode_step(
        p, CFG, jnp.zeros((rows,), jnp.int32), c, jnp.int32(40),
        jnp.int32(3)))(params, caches)
    shapes = set(_shapes(program.jaxpr))
    sized = [s for s in shapes
             if len(s) >= 3 and s[-1] in (capacity, capacity + max_new)
             and np.prod(s) >= rows * 4 * capacity]
    assert "pallas_call" in str(program) and not sized, sized
    # the sliding layers' scores: the window's 8 and the suffix's 16
    assert (rows, 2, 2, 8 + max_new) in shapes


# ---- experts ---------------------------------------------------------------


def test_the_router_is_softmax_top_k_normalised_and_scaled(sizes):
    """Softmax over all 16, the 3 largest, weights normalised to sum 1
    and times 2.5: against the reference's loop; ties to the lower
    index."""
    layer = laguna.random_params(CFG, seed=4)["layers"][2]["mlp"]
    x = jnp.asarray(np.random.RandomState(7).randn(40, CFG.hidden_size),
                    jnp.float32)
    chosen, weight = laguna.route(layer, CFG, x)
    with jax.default_matmul_precision("highest"):
        want_chosen, want_weight = ref.route(layer, sizes, x)
        probs = np.asarray(jax.nn.softmax(x @ layer["router"], -1))
    assert np.array_equal(np.asarray(chosen), want_chosen)
    assert np.allclose(np.asarray(weight), want_weight, rtol=1e-5)
    assert np.allclose(np.asarray(weight).sum(-1),
                       CFG.moe_routed_scaling_factor, rtol=1e-5)
    assert np.array_equal(np.asarray(chosen)[:, 0], probs.argmax(-1))
    # equal logits: the lower index wins
    tied = dict(layer, router=jnp.zeros_like(layer["router"]))
    chosen, weight = laguna.route(tied, CFG, x[:2])
    assert np.array_equal(np.asarray(chosen), [[0, 1, 2]] * 2)
    assert np.allclose(np.asarray(weight), 2.5 / 3)


def test_the_four_expert_shares_add_up_to_the_uncut_layer(sizes):
    """Four chips of four experts each: the parts their held experts
    give, with the shared expert (which every chip computes alike)
    counted once, are the whole layer of the uncut reference."""
    whole = dataclasses.replace(CFG, experts_held=(0, CFG.num_experts))
    layer = laguna.random_params(whole, seed=4)["layers"][3]["mlp"]
    x = jnp.asarray(np.random.RandomState(6).randn(24, CFG.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe_layer(layer, sizes, x, held=(0, 16)))
        shared = np.asarray(ref.swiglu(layer["shared"], x))
    total = np.zeros_like(want)
    pairs_held = 0
    for first in range(0, CFG.num_experts, 4):
        cfg = dataclasses.replace(CFG, experts_held=(first, first + 4))
        share = dict(layer, experts={
            name: mat[first:first + 4]
            for name, mat in layer["experts"].items()})
        y, stats = laguna.moe(share, cfg, x)
        # one share alone is what the reference gives for that share
        with jax.default_matmul_precision("highest"):
            alone = np.asarray(ref.moe_layer(share, sizes, x,
                                             held=(first, first + 4)))
        assert np.abs(np.asarray(y) - alone).max() < LAYER_TOL
        total += np.asarray(y) - shared
        pairs_held += int(stats["pairs_held"])
        assert int(stats["pairs"]) == 24 * CFG.num_experts_per_tok
    assert np.abs(total + shared - want).max() < LAYER_TOL
    assert pairs_held == 24 * CFG.num_experts_per_tok
    # a layer that skipped its shared expert would miss by far more
    assert np.abs(total - want).max() > 100 * LAYER_TOL
    # the whole layer in one piece, as the cell holds it
    y, stats = laguna.moe(layer, whole, x)
    assert np.abs(np.asarray(y) - want).max() < LAYER_TOL
    assert int(stats["pairs_held"]) == int(stats["pairs"])


# ---- the stack behind the pipeline, the registry and the worker ------------


def words(ids):
    from chiaswarm_tpu.pipelines.text import word_vocab

    vocab = {i: w for w, i in word_vocab(CFG.vocab_size).items()}
    return " ".join(vocab[int(i)] for i in ids)


PROMPT = words(np.random.RandomState(0).randint(0, 96, 19))


def test_the_stack_is_found_by_the_name_its_configuration_gives():
    assert text_stacks.get(CFG.stack) is laguna
    assert "laguna" in text_stacks.NAMES and laguna.TINY.stack == "laguna"
    for needed in ("param_shapes", "random_params", "param_bytes",
                   "empty_prefill_caches", "prefill_chunk",
                   "decode_caches", "decode_step", "empty_stats",
                   "cache_bytes", "job_counts"):
        assert callable(getattr(laguna, needed)), needed


def test_the_pipeline_and_the_workload_name_no_model():
    """PR 33's rule: an argument, never a branch on the model. Nothing in
    the two files names a stack, and only ``text_stacks.get`` imports
    this one."""
    import pathlib
    import re

    root = pathlib.Path(laguna.__file__).resolve().parents[1]
    for name in ("pipelines/text.py", "workloads/text.py"):
        text = (root / name).read_text().lower()
        for word in ("laguna", "deepseek", "ling"):
            assert not re.search(rf"\b{word}\b", text), (name, word)
    importers = [p for p in root.rglob("*.py")
                 if re.search(r"(import|from)\s+[\w.]*\blaguna\b",
                              p.read_text())]
    assert importers == []


def test_token_logprobs_of_the_pipeline_are_the_references():
    """The served log-probabilities of the sampled tokens against the
    reference's full forward over prompt + those tokens (float32 both:
    rounding, 1e-4 with room; a wrong token would be off by whole
    nats), with more new tokens than the window holds."""
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    pipe = TextPipeline(TextComponents.random(laguna.TINY, seed=2),
                        prefill_chunk=16, max_context=64)
    assert pipe.c.stack is laguna
    assert pipe.c.model_name == "random/laguna_tiny"
    out = pipe(PROMPT, seed=5, max_new_tokens=11, num_return_sequences=2,
               logprobs=True)
    prompt_ids = pipe.tokenize(PROMPT)
    sizes = ref.sizes_of(pipe.c.config)
    for seq in out["sequences"]:
        new = pipe.c.tokenizer.tokenize(seq["text"])
        assert len(new) == len(seq["token_logprobs"]) == 11
        logits = np.asarray(ref.forward(
            pipe.c.params, sizes, np.concatenate([prompt_ids, new])),
            np.float64)[len(prompt_ids) - 1:-1]
        norm = np.log(np.exp(logits).sum(-1))
        want = logits[np.arange(11), new] - norm
        assert np.abs(want - np.asarray(seq["token_logprobs"])).max() < 1e-4


def test_an_unmodified_worker_settles_a_txt2txt_job_of_the_third_stack():
    """Polled, run and settled through the worker's normal path; the
    catalog entry names the stack, the four text spans carry it, and the
    counter families moved by what this stack's programs returned, the
    window's family among them."""
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.obs.metrics import REGISTRY

    registry = ModelRegistry(
        catalog=[{"name": "tiny/laguna", "stack": "laguna",
                  "prefill_chunk": 16, "max_context": 64}],
        allow_random=True)

    def counters():
        snap = REGISTRY.snapshot()
        return {name: dict(snap[name]["values"]) for name in (
            "chiaswarm_text_tokens_total",
            "chiaswarm_moe_routed_pairs_total",
            "chiaswarm_moe_experts_hit_total",
            "chiaswarm_moe_layer_steps_total",
            "chiaswarm_text_prefill_key_blocks_total",
            "chiaswarm_text_prefill_block_steps_total",
            "chiaswarm_text_decode_key_blocks_total",
            "chiaswarm_text_attention_pairs_total",
            "chiaswarm_text_window_pairs_total",
            "chiaswarm_text_kda_blocks_total",
            "chiaswarm_text_cache_bytes")}

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
            hive.submit({"id": "hive-lg", "workflow": "txt2txt",
                         "model_name": "tiny/laguna", "prompt": PROMPT,
                         "seed": 9, "max_new_tokens": 12,
                         "num_return_sequences": 2, "logprobs": True,
                         "content_type": "application/json"})
            await hive.wait_for_results(1, timeout=300)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=60)
            await hive.stop()
        return hive.results[0], hive.flights.get("hive-lg")

    before = counters()
    result, record = asyncio.run(scenario())
    after = counters()
    assert "error" not in result["pipeline_config"], result
    payload = json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))
    assert len(payload["sequences"]) == 2
    assert all(len(s["token_logprobs"]) == 12 for s in payload["sequences"])
    assert registry.text_pipeline("tiny/laguna").c.stack is laguna
    spans = {s["name"]: s
             for s in record["attempts"][-1]["digest"]["spans"]}
    for name in ("text.tokenize", "text.prefill", "text.decode",
                 "text.detokenize"):
        assert spans[name]["phase"] == "execute" and spans[name]["dur_s"] > 0

    def moved(family, key):
        return after[family].get(key, 0) - before[family].get(key, 0)

    want = laguna.job_counts(CFG, 19, 2, 16, 16, 64)
    tokens = "chiaswarm_text_tokens_total"
    assert moved(tokens, "prefill") == 19
    assert moved(tokens, "decode") == 2 * 16    # the 16-token bucket
    pairs = "chiaswarm_moe_routed_pairs_total"
    k, layers = CFG.num_experts_per_tok, 5
    assert moved(pairs, "prefill,yes") + moved(pairs, "prefill,no") \
        == 19 * k * layers
    assert moved(pairs, "decode,yes") + moved(pairs, "decode,no") \
        == 2 * 15 * k * layers
    hit = moved("chiaswarm_moe_experts_hit_total", "")
    assert 0 < hit <= moved(pairs, "decode,yes")
    assert moved("chiaswarm_moe_layer_steps_total", "") == 15 * layers
    seen = "chiaswarm_text_attention_pairs_total"
    assert (moved(seen, "prefill"), moved(seen, "decode")) \
        == want["attention_pairs"]
    window = "chiaswarm_text_window_pairs_total"
    assert moved(window, "visible") == want["window_pairs"]["visible"] > 0
    assert moved(window, "scored") == want["window_pairs"]["scored"] \
        >= moved(window, "visible")
    blocks = "chiaswarm_text_prefill_key_blocks_total"
    assert moved(blocks, "yes") == want["key_blocks"][0]
    # the grouped and the windowed sweep's grid steps: with rows that
    # share a position every crossed pair is masked whole, one a pair
    steps = "chiaswarm_text_prefill_block_steps_total"
    assert {kind: moved(steps, kind) for kind in want["block_steps"]} \
        == want["block_steps"]
    assert want["block_steps"]["diagonal"] > 0
    blocks = "chiaswarm_text_decode_key_blocks_total"
    assert moved(blocks, "yes") == 2 * 15
    kda = "chiaswarm_text_kda_blocks_total"
    assert (moved(kda, "pairwise"), moved(kda, "product")) == (0, 0)
    bytes_ = after["chiaswarm_text_cache_bytes"]
    assert (bytes_["full"], bytes_["window"]) == (
        2 * (64 + 2 * 16) * 256, 4 * (8 + 2 * 16) * 256)


def test_the_spans_carry_the_stacks_name():
    from chiaswarm_tpu.obs.trace import JobTrace
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    pipe = TextPipeline(TextComponents.random(laguna.TINY, seed=2),
                        prefill_chunk=16, max_context=64)
    trace = JobTrace()
    with trace.active():
        pipe(PROMPT, seed=1, max_new_tokens=2)
    by_name = {s.name: s for s in trace.root.children}
    for name in ("text.tokenize", "text.prefill", "text.decode",
                 "text.detokenize"):
        assert by_name[name].meta["stack"] == "laguna"


def test_the_smoke_job_of_the_third_stack_settles():
    """``python -m chiaswarm_tpu.node.smoke --workflow txt2txt_laguna``:
    the hard-coded job through the real dispatch path, the stack named by
    the smoke registry's catalog entry."""
    from chiaswarm_tpu.node.smoke import run_smoke

    result = run_smoke("txt2txt_laguna")
    assert "error" not in result["pipeline_config"], result
    payload = json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))
    assert len(payload["sequences"]) == 2
    assert all(len(s["token_logprobs"]) == 4 for s in payload["sequences"])
