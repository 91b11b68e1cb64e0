"""The Solar-Open2-class decoder (models/solar.py) against the benchmark's
plain float32 reference (perfbench/solarref.py) on seeded weights at the
tiny size: hidden 64, six expert layers (G K K K G K), softmax layers of
8 query heads over 2 key-value heads of 16 with no position encoding,
delta-rule layers of 2 heads of 8 with b up to 2 and the softplus decay,
16 sigmoid-routed experts of which 4 are held, float32 weights.

Tolerances. Program and reference compute the same function in float32
in another order (the UT-form chunks against a token-by-token scan,
grouped heads, a shared prompt joined to a suffix, grouped experts), so
they differ by rounding: logits of magnitude ~3 agree to a few 1e-6. The
limits sit a decade above that, and the tests show that the same
computation with bfloat16-rounded operands (relative step 2^-8) misses
its limit by a wide margin: a lower precision cannot hide inside them.
"""

import asyncio
import base64
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models import ling, solar, text_layers, text_stacks
from chiaswarm_tpu.ops import causal_flash_attention as kernel
from perfbench import solarref

CFG = solar.TINY
LOGIT_TOL = 5e-5      # |logit| ~ 3: a few float32 roundings, ~10x room
LAYER_TOL = 2e-5      # one layer's output, magnitude ~1
KERNEL_TOL = 5e-6     # one softmax read-out of unit values
#: the delta rule at b near 2: along k the transition's eigenvalue 1 - b
#: lies near -1, so float32 rounding no longer decays over the tokens but
#: accumulates (2.7e-5 read over 128 tokens against a float64 scan)
NEAR_2_TOL = 1e-4


def bf16(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def sizes_of(cfg):
    """The reference's view of a configuration, as a configuration
    file would hold it."""
    return solarref.sizes(dataclasses.asdict(cfg))


@pytest.fixture(scope="module")
def params():
    return solar.random_params(CFG, seed=3)


@pytest.fixture(scope="module")
def sizes():
    return sizes_of(CFG)


def prefill(params, cfg, ids, chunk, capacity=64):
    caches = solar.empty_prefill_caches(cfg, capacity)
    fn = jax.jit(lambda p, i, c, pos, n: solar.prefill_chunk(
        p, cfg, i, c, pos, n))
    for pos in range(0, len(ids), chunk):
        part = np.zeros((1, chunk), np.int32)
        n = min(chunk, len(ids) - pos)
        part[0, :n] = ids[pos:pos + n]
        logits, caches, stats = fn(params, jnp.asarray(part), caches,
                                   jnp.int32(pos), jnp.int32(n))
    return logits, caches, stats


def published():
    """The cell's cut of the published configuration: layers 0-5, experts
    0-39 of 320, vocabulary rows 0-24,575, bfloat16."""
    return dataclasses.replace(solar.SolarConfig(), num_hidden_layers=6,
                               experts_held=(0, 40), vocab_size=24576)


# ---- the preset and the layout ---------------------------------------------


def test_the_tiny_preset_holds_every_case_the_stack_has():
    assert [CFG.is_gqa(i) for i in range(6)] == [True, False, False, False,
                                                 True, False]
    assert CFG.kda_layers == [1, 2, 3, 5] and CFG.softmax_layers == [0, 4]
    assert all(CFG.is_moe(i) for i in range(6))
    assert (CFG.n_routed_experts, CFG.experts_held) == (16, (0, 4))
    rule = CFG.delta_rule
    assert (rule.heads, rule.head_dim, rule.conv) == (2, 8, 4)
    assert rule.lower_bound is None and rule.allow_neg_eigval
    # a UT chunk of 32: two row blocks of 16, both forms of the matrices
    assert text_layers.kda_block(rule.chunk) == 16
    assert CFG.num_attention_heads // CFG.num_key_value_heads == 4
    assert ling.TINY.delta_rule.lower_bound == -5.0
    assert not ling.TINY.delta_rule.allow_neg_eigval
    for wrong in ({"use_rope": True}, {"use_gqa_gate": False},
                  {"kda_use_full_proj": True}, {"num_key_value_heads": 3}):
        with pytest.raises(ValueError):
            dataclasses.replace(CFG, **wrong)


def test_the_layout_is_the_published_one_at_the_cut():
    """The benchmark's cut: layers 0-5 (G K K K G K), 40 of 320 experts
    and an eighth of the vocabulary: 4.85 B parameters, 9.71 GB in
    bfloat16 (the routers float32); the whole model 250 B."""
    cfg = published()

    def count(c):
        return sum(int(np.prod(s.shape))
                   for s in jax.tree.leaves(solar.param_shapes(c)))

    def size(c):
        return sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree.leaves(solar.param_shapes(c)))

    assert 4.84e9 < count(cfg) < 4.86e9
    assert 9.70e9 < size(cfg) < 9.72e9
    assert 249e9 < count(solar.SolarConfig()) < 252e9
    shapes = solar.param_shapes(cfg)
    gqa, kda = shapes["layers"][4]["attn"], shapes["layers"][5]["attn"]
    assert gqa["wq"].shape == gqa["wgate"].shape == (4096, 64 * 128)
    assert gqa["wk"].shape == gqa["wv"].shape == (4096, 8 * 128)
    assert gqa["wo"].shape == (64 * 128, 4096)
    assert kda["wq"].shape == (4096, 64 * 128)
    assert kda["conv_k"].shape == (4, 64 * 128)
    # both gates a low-rank pair of rank 128
    assert (kda["wa_down"].shape, kda["wa_up"].shape) \
        == ((4096, 128), (128, 8192))
    assert (kda["wg_down"].shape, kda["wg_up"].shape) \
        == ((4096, 128), (128, 8192))
    assert kda["a_log"].shape == (64,) and kda["dt_bias"].shape == (8192,)
    assert kda["wb"].shape == (4096, 64)
    mlp = shapes["layers"][0]["mlp"]
    assert mlp["experts"]["gate"].shape == (40, 4096, 1280)
    assert mlp["router"].shape == (4096, 320)
    assert mlp["router"].dtype == jnp.float32
    assert mlp["shared"]["down"].shape == (1280, 4096)
    assert shapes["head"].shape == (4096, 24576)
    caches = jax.eval_shape(lambda: solar.empty_prefill_caches(cfg, 16384))
    assert [c[0].shape for c in caches["kda"]] == [(1, 64, 128, 128)] * 4
    assert [c["k"].shape for c in caches["kv"]] == [(1, 16384, 8, 128)] * 2


def test_the_two_kinds_of_cache_and_their_bytes():
    """A delta-rule layer's state does not grow with the capacity or the
    new tokens; a softmax layer's keys and values do."""
    state = 2 * 8 * 8 * 4 + 3 * 3 * 2 * 8 * 4      # float32 TINY
    entry = 2 * 2 * 16 * 4
    for capacity in (64, 1024):
        assert solar.cache_bytes(CFG, 3, capacity, 16) == {
            "recurrent": 4 * 3 * state,
            "full": 2 * (capacity + 3 * 16) * entry}
    cfg = published()
    got = solar.cache_bytes(cfg, 16, 16384, 64)
    # 4 MiB of float32 state a row and layer, plus three conv tails
    assert got["recurrent"] == 4 * 16 * (64 * 128 * 128 * 4
                                         + 3 * 3 * 8192 * 2)
    assert got["full"] == 2 * (16384 + 16 * 64) * 2 * 8 * 128 * 2


# ---- the shared delta-rule core --------------------------------------------


def _recurrence64(q, k, v, g, b, state):
    """The delta rule token by token in float64 on the host."""
    q, k, v, g, b, s = (np.asarray(x, np.float64)
                        for x in (q, k, v, g, b, state))
    outs = []
    for t in range(q.shape[0]):
        s = np.exp(g[t])[..., None] * s
        pred = np.einsum("hk,hkv->hv", k[t], s)
        s = s + b[t][:, None, None] * k[t][..., None] \
            * (v[t] - pred)[:, None, :]
        outs.append(np.einsum("hk,hkv->hv", q[t], s))
    return np.stack(outs), s


@pytest.mark.parametrize("chunk, t", [(4, 16), (32, 96), (64, 128)],
                         ids=["chunk4", "chunk32", "chunk64"])
def test_the_chunked_form_is_the_recurrence_at_b_near_2_and_steep_decays(
        chunk, t):
    """Write strengths in (1.9, 2) (the transition's eigenvalue 1 - b
    near -1 along the key) and softplus log-decays from -1e-4 down to
    -30 a token: a 64-token chunk's running decay reaches e^-1920, which
    the pairwise and the rescaled product forms carry without overflow.
    The UT system stays unit lower-triangular; every output is finite
    and the chunked form is the recurrence of the program's own decode
    step and of a float64 scan."""
    rng = np.random.RandomState(chunk)
    h, d = 2, 8
    q, k, v = (rng.randn(1, t, h, d).astype(np.float32) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    g = -np.exp(rng.uniform(np.log(1e-4), np.log(30.0), (1, t, h, d)))
    b = rng.uniform(1.9, 2.0, (1, t, h))
    state = rng.randn(1, h, d, d)
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b, state)]
    o, s = text_layers.kda_chunked(*args, chunk=chunk)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s)).all()
    want_o, want_s = _recurrence64(q[0], k[0], v[0], g[0], b[0], state[0])
    assert np.abs(np.asarray(o[0]) - want_o).max() < NEAR_2_TOL
    assert np.abs(np.asarray(s[0]) - want_s).max() < NEAR_2_TOL
    step = jax.jit(text_layers.kda_recurrent_step)
    step_s, outs = args[-1], []
    for i in range(t):
        out, step_s = step(*(x[:, i] for x in args[:-1]), step_s)
        outs.append(out)
    assert np.abs(np.asarray(jnp.stack(outs, 1)[0]) - want_o).max() \
        < NEAR_2_TOL
    # bfloat16 operands miss the limit, and b not doubled is another
    # function
    o16 = text_layers.kda_chunked(*bf16(args[:3]), *args[3:], chunk=chunk)[0]
    assert np.abs(np.asarray(o16[0]) - want_o).max() > 10 * NEAR_2_TOL
    half = text_layers.kda_chunked(*args[:4], args[4] / 2, args[5],
                                   chunk=chunk)[0]
    assert np.abs(np.asarray(half[0]) - want_o).max() > 100 * NEAR_2_TOL


@pytest.mark.parametrize("option", ["beta", "decay"])
def test_each_option_of_the_core_changes_its_function(option):
    """The two options a configuration gives the core: b = 2 sigmoid(.)
    against sigmoid(.), and the softplus decay against the bounded gate;
    the same weights through both read apart far past the tolerance."""
    rule = CFG.delta_rule
    other = dataclasses.replace(
        rule, **({"allow_neg_eigval": False} if option == "beta"
                 else {"lower_bound": -5.0}))
    p = solar.random_params(CFG, 4)["layers"][1]["attn"]
    x = jnp.asarray(np.random.RandomState(5).randn(1, 32, 64), jnp.float32)
    cache = text_layers.kda_empty_cache(rule, 1, "float32")
    q, k, v, g, beta, _ = text_layers.kda_inputs(p, rule, x, cache[1], 32)
    if option == "beta":
        assert float(beta.max()) > 1.0 and float(beta.min()) > 0.0
        assert 0.2 < float((beta > 1.0).mean()) < 0.8
    else:
        assert float(g.max()) <= 0.0 and float(g.min()) > -20.0
    a, _ = text_layers.kda_prefill(p, rule, x, cache, 32)
    b, _ = text_layers.kda_prefill(p, other, x, cache, 32)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 100 * LAYER_TOL


def test_a_gate_projection_is_one_matrix_or_a_low_rank_pair():
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(3, 64), jnp.float32)
    down, up = (jnp.asarray(rng.randn(*s), jnp.float32)
                for s in ((64, 8), (8, 16)))
    pair = text_layers.gate_proj({"wa_down": down, "wa_up": up}, "wa", x)
    whole = text_layers.gate_proj({"wa": down @ up}, "wa", x)
    assert np.abs(np.asarray(pair) - np.asarray(whole)).max() < 1e-4


@pytest.mark.parametrize("cfg, prompt, chunk, pairwise, product", [
    (CFG, 37, 32, 4 * 2 * 2, 4 * 2 * 1),
    (CFG, 37, 16, 4 * 3 * 1, 0),
    (published(), 16384, 2048, 4 * 8 * 32 * 4, 4 * 8 * 32 * 6)],
    ids=["tiny-two-chunks-of-32", "tiny-chunks-of-16", "the-cell"])
def test_the_delta_rule_sub_blocks_by_form(cfg, prompt, chunk, pairwise,
                                          product):
    assert text_layers.kda_blocks(cfg.delta_rule, len(cfg.kda_layers),
                                  prompt, chunk) == (pairwise, product)


def test_the_recurrent_state_bytes_by_phase():
    """Each prefill chunk reads and writes one row's state and tails once
    a layer, each decode step every row's: the cell's job 16,384 tokens
    in 8 chunks, 16 rows x 63 steps, 4 layers of 4 MiB + 147 KiB."""
    counts = solar.job_counts(CFG, 37, 2, 16, 16, 64)
    one = 2 * 8 * 8 * 4 + 3 * 3 * 2 * 8 * 4
    assert counts["recurrent_state_bytes"] == {
        "prefill": 4 * 3 * 2 * one, "decode": 4 * 15 * 2 * 2 * one}
    cfg = published()
    big = solar.job_counts(cfg, 16384, 16, 64, 2048, 16384)
    one = 64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2
    assert big["recurrent_state_bytes"] == {
        "prefill": 4 * 8 * 2 * one, "decode": 4 * 63 * 16 * 2 * one}
    assert 0.55e9 < big["recurrent_state_bytes"]["decode"] / 63 < 0.56e9
    # Ling's seven KDA layers, 32 rows, 127 steps
    lcfg = dataclasses.replace(ling.LingConfig(), num_hidden_layers=8)
    lcounts = ling.job_counts(lcfg, 16384, 32, 128, 2048, 16384)
    one = 32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2
    assert lcounts["recurrent_state_bytes"]["decode"] \
        == 7 * 127 * 32 * 2 * one


# ---- the whole stack -------------------------------------------------------


@pytest.mark.parametrize("n_prompt, n_new, chunk", [
    (37, 14, 32),       # two chunks, the second padded; both UT forms
    (32, 10, 16),       # two whole chunks of 16
    (5, 12, 16),        # a prompt shorter than a chunk and the conv tail
], ids=["37+14", "32+10", "5+12"])
def test_prefill_then_cached_decode_is_the_full_forward(params, sizes,
                                                        n_prompt, n_new,
                                                        chunk):
    """The prompt in chunks through both kinds of cache, then
    teacher-forced tokens on two rows: every position's logits against
    one uncached pass of the reference."""
    rng = np.random.RandomState(n_prompt)
    ids = rng.randint(0, CFG.vocab_size, n_prompt + n_new)
    want = np.asarray(solarref.forward(params, sizes, ids))
    assert np.abs(want).max() > 0.5
    logits, caches, _ = prefill(params, CFG, ids[:n_prompt], chunk=chunk)
    assert np.abs(np.asarray(logits[0]) - want[n_prompt - 1]).max() \
        < LOGIT_TOL
    caches = solar.decode_caches(CFG, caches, 2, 16)
    step = jax.jit(lambda p, t, c, n, s: solar.decode_step(
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


@pytest.mark.parametrize("fault", [
    "b-not-doubled", "bounded-gate", "wrong-kv-head", "no-gate",
    "head-wise-gate", "rotated"])
def test_what_this_stack_tells_the_shared_cores_matters(params, sizes, fault,
                                                        monkeypatch):
    """Each reading of the config that the program could get wrong moves
    the logits far past the tolerance (so the agreement above is not
    blind to it)."""
    ids = np.random.RandomState(5).randint(0, CFG.vocab_size, 30)
    want = np.asarray(solarref.forward(params, sizes, ids))
    cfg = CFG
    if fault == "b-not-doubled":
        cfg = dataclasses.replace(CFG, kda_allow_neg_eigval=False)
    if fault == "bounded-gate":
        rule = dataclasses.replace(CFG.delta_rule, lower_bound=-5.0)
        monkeypatch.setattr(solar.SolarConfig, "delta_rule",
                            property(lambda self: rule))
    if fault == "wrong-kv-head":
        qkv = text_layers._queries_keys_values

        def rolled(p, x, positions, heads, kv_heads, *rest):
            q, k, v = qkv(p, x, positions, heads, kv_heads, *rest)
            return q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2)

        monkeypatch.setattr(text_layers, "_queries_keys_values", rolled)
    if fault == "no-gate":
        monkeypatch.setattr(solar, "_out", lambda p, x, o: text_layers.proj(
            o.reshape(*x.shape[:2], -1), p["wo"]))
    if fault == "head-wise-gate":
        def head_wise(p, x, o):
            gate = jax.nn.sigmoid(text_layers.proj(x, p["wgate"]))
            gate = gate.reshape(o.shape).mean(-1, keepdims=True)
            return text_layers.proj((o * gate).reshape(*x.shape[:2], -1),
                                    p["wo"])

        monkeypatch.setattr(solar, "_out", head_wise)
    if fault == "rotated":
        told = solar._told
        monkeypatch.setattr(solar, "_told", lambda c: dict(
            told(c), inv_freq=text_layers.rope_frequencies(1e4, c.head_dim)))
    logits, _, _ = prefill(params, cfg, ids, chunk=32)
    assert np.abs(np.asarray(logits[0]) - want[-1]).max() > 100 * LOGIT_TOL


def test_the_nope_layer_reads_the_same_keys_alike_wherever_they_lie(params):
    """No position encoding: the last query's read-out over the same
    keys and values is the same whatever positions they sit at (the 30
    tokens before it shuffled across the two chunks: keys that sat at 3
    now sit at 20), as long as the causal mask shows them all. A rotary
    table in the core's place makes the positions count."""
    layer = params["layers"][0]["attn"]
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(1, 32, CFG.hidden_size), jnp.float32)
    order = np.concatenate([rng.permutation(31), [31]])
    told = solar._told(CFG)

    def last(x, inv_freq=None):
        cache = text_layers.empty_kv_cache(CFG, 64)
        for pos in (0, 16):
            o, cache = text_layers.kv_prefill(
                layer, x[:, pos:pos + 16], cache, jnp.int32(pos),
                jnp.int32(16), **dict(told, inv_freq=inv_freq))
        return np.asarray(o[0, -1])

    assert np.abs(last(x) - last(x[:, order])).max() < KERNEL_TOL
    freq = text_layers.rope_frequencies(1e4, CFG.head_dim)
    assert np.abs(last(x, freq) - last(x[:, order], freq)).max() \
        > 1000 * KERNEL_TOL
    # the decode: 16 prompt keys and the row's own in its suffix, joined
    # exactly, are the dense softmax over the 17
    cache = text_layers.empty_kv_cache(CFG, 64)
    _, cache = text_layers.kv_prefill(layer, x[:, :16], cache, jnp.int32(0),
                                      jnp.int32(16), **told)
    o_b, _ = text_layers.kv_decode(layer, x[:, 16:17], cache, jnp.int32(16),
                                   text_layers.empty_kv_cache(CFG, 8, 1),
                                   jnp.int32(0), **told)
    keys = text_layers._queries_keys_values(
        layer, x[:, :17], None, CFG.num_attention_heads, 2, None, 1.0)
    g = CFG.num_attention_heads // 2
    kk, vv = (jnp.repeat(z[0], g, axis=1) for z in keys[1:])
    scores = jnp.einsum("hd,shd->hs", keys[0][0, 16], kk) * 16 ** -0.5
    want = jnp.einsum("hs,shd->hd", jax.nn.softmax(scores, -1), vv)
    assert np.abs(np.asarray(o_b[0, 0]) - np.asarray(want)).max() \
        < KERNEL_TOL


# ---- the kernel entries at 8 query heads a key-value head ------------------


def dense(q, k, v, offset, scale):
    g = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("blhd,bshd->bhls", q, kk) * scale
    row = offset + jnp.arange(q.shape[1])[:, None]
    seen = jnp.arange(k.shape[1])[None] <= row
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhls,bshd->blhd", weights, vv)


@pytest.mark.parametrize("offset", [0, 24])
def test_the_grouped_prefill_at_eight_heads_a_group(offset):
    """The cell's group size (64 query heads over 8 key-value heads):
    the grouped causal sweep against the dense masked form, NaN past
    the written cache."""
    rng = np.random.RandomState(offset)
    q = jnp.asarray(rng.randn(1, 16, 16, 16), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32
                        ).at[:, offset + 16:].set(jnp.nan) for _ in range(2))
    got = kernel.causal_flash_attention(q, k, v, jnp.int32(offset),
                                        scale=0.25, block_q=32, block_kv=16)
    want = dense(q, jnp.nan_to_num(k), jnp.nan_to_num(v), offset, 0.25)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < KERNEL_TOL


def test_the_shared_prompt_sweep_at_eight_heads_a_group():
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(3, 16, 16), jnp.float32)
    k, v = (jnp.asarray(rng.randn(48, 2, 16), jnp.float32
                        ).at[33:].set(jnp.nan) for _ in range(2))
    o, lse = kernel.shared_prompt_attention(q, k, v, jnp.int32(33),
                                            scale=0.25, block_kv=16)
    kk, vv = (jnp.repeat(jnp.nan_to_num(z), 8, axis=1) for z in (k, v))
    scores = jnp.einsum("rhd,shd->rhs", q, kk) * 0.25
    scores = jnp.where(jnp.arange(48) < 33, scores, -jnp.inf)
    want = jnp.einsum("rhs,shd->rhd", jax.nn.softmax(scores, -1), vv)
    assert np.abs(np.asarray(o) - np.asarray(want)).max() < KERNEL_TOL
    assert np.abs(np.asarray(lse) - np.asarray(
        jax.nn.logsumexp(scores, -1))).max() < KERNEL_TOL


def test_job_counts_are_a_brute_force_count():
    """Pairs a head scores, by phase, over the two softmax layers; their
    prefill grid steps and decode key blocks; the delta-rule layers'
    sub-blocks and state bytes; six expert layers."""
    p, rows, new, chunk, capacity = 37, 2, 16, 16, 64
    counts = solar.job_counts(CFG, p, rows, new, chunk, capacity)
    steps = new - 1
    assert counts["attention_pairs"] == (
        2 * sum(t + 1 for t in range(p)),
        2 * rows * sum(p + s + 1 for s in range(steps)))
    # 2 key-value heads of 4 query heads' rows: chunk i (64-row blocks
    # against 16-key ones) steps i whole pairs and one across the diagonal
    assert counts["block_steps"] == {"whole": 2 * 2 * (0 + 1 + 2),
                                     "diagonal": 2 * 2 * 3, "dead": 0}
    assert counts["decode_key_blocks"] == (2 * steps, 2 * steps)
    assert counts["kda_blocks"] == (4 * 3, 0)
    assert counts["expert_layers"] == 6
    assert "window_pairs" not in counts and "up_projections" not in counts


# ---- experts ---------------------------------------------------------------


def test_the_router_is_sigmoid_top_k_with_one_group(sizes):
    layer = solar.random_params(CFG, seed=4)["layers"][2]["mlp"]
    x = jnp.asarray(np.random.RandomState(7).randn(40, CFG.hidden_size),
                    jnp.float32)
    chosen, weight = solar.route(layer, CFG, x)
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    best = np.argsort(-(scores + np.asarray(layer["router_bias"])), -1,
                      kind="stable")[:, :4]
    assert np.array_equal(np.sort(np.asarray(chosen), -1),
                          np.sort(best, -1))
    assert np.allclose(np.asarray(weight).sum(-1), 1.0, rtol=1e-5)
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    assert np.allclose(np.asarray(weight), picked / picked.sum(-1,
                                                               keepdims=True),
                       rtol=1e-5)


def test_the_eight_expert_shares_add_up_to_the_uncut_layer(sizes):
    """Eight chips of two experts each: the parts their held experts
    give, with the shared expert (which every chip computes alike)
    counted once, are the whole layer of the uncut reference."""
    whole = dataclasses.replace(CFG, experts_held=(0, 16))
    layer = solar.random_params(whole, seed=4)["layers"][3]["mlp"]
    x = jnp.asarray(np.random.RandomState(6).randn(24, CFG.hidden_size),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(solarref.moe_layer(layer, sizes, x, "float32",
                                            held=(0, 16)))
        shared = np.asarray(solarref.moe_layer(layer, sizes, x, "float32",
                                               held=(0, 0)))
    total = np.zeros_like(want)
    pairs_held = 0
    for first in range(0, 16, 2):
        cfg = dataclasses.replace(CFG, experts_held=(first, first + 2))
        share = dict(layer, experts={
            name: mat[first:first + 2]
            for name, mat in layer["experts"].items()})
        y, stats = solar.moe(share, cfg, x)
        total += np.asarray(y) - shared
        pairs_held += int(stats["pairs_held"])
    assert np.abs(total + shared - want).max() < LAYER_TOL
    assert pairs_held == 24 * CFG.num_experts_per_tok
    assert np.abs(total - want).max() > 100 * LAYER_TOL


# ---- the stack behind the pipeline, the registry and the worker ------------


def words(ids):
    from chiaswarm_tpu.pipelines.text import word_vocab

    vocab = {i: w for w, i in word_vocab(CFG.vocab_size).items()}
    return " ".join(vocab[int(i)] for i in ids)


PROMPT = words(np.random.RandomState(0).randint(0, 96, 19))


def test_the_stack_is_found_by_the_name_its_configuration_gives():
    assert text_stacks.get(CFG.stack) is solar
    assert "solar" in text_stacks.NAMES
    for needed in ("param_shapes", "random_params", "param_bytes",
                   "empty_prefill_caches", "prefill_chunk",
                   "decode_caches", "decode_step", "empty_stats",
                   "cache_bytes", "job_counts"):
        assert callable(getattr(solar, needed)), needed


def test_token_logprobs_of_the_pipeline_are_the_references():
    """The served log-probabilities of the sampled tokens against the
    reference's full forward over prompt + those tokens (float32 both:
    rounding, 1e-4 with room; a wrong token would be off by whole
    nats)."""
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    pipe = TextPipeline(TextComponents.random(solar.TINY, seed=2),
                        prefill_chunk=16, max_context=64)
    assert pipe.c.stack is solar
    assert pipe.c.model_name == "random/solar_tiny"
    out = pipe(PROMPT, seed=5, max_new_tokens=11, num_return_sequences=2,
               logprobs=True)
    prompt_ids = pipe.tokenize(PROMPT)
    sizes = sizes_of(pipe.c.config)
    for seq in out["sequences"]:
        new = pipe.c.tokenizer.tokenize(seq["text"])
        assert len(new) == len(seq["token_logprobs"]) == 11
        logits = np.asarray(solarref.forward(
            pipe.c.params, sizes, np.concatenate([prompt_ids, new])),
            np.float64)[len(prompt_ids) - 1:-1]
        norm = np.log(np.exp(logits).sum(-1))
        want = logits[np.arange(11), new] - norm
        assert np.abs(want - np.asarray(seq["token_logprobs"])).max() < 1e-4


def test_an_unmodified_worker_settles_a_txt2txt_job_of_the_fourth_stack():
    """Polled, run and settled through the worker's normal path; the
    catalog entry names the stack, and the counter families moved by
    what this stack's programs returned and its host counts: the
    delta-rule families (fed by two stacks now) among them."""
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.obs.metrics import REGISTRY

    registry = ModelRegistry(
        catalog=[{"name": "tiny/solar", "stack": "solar",
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
            hive.submit({"id": "hive-so", "workflow": "txt2txt",
                         "model_name": "tiny/solar", "prompt": PROMPT,
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
    assert registry.text_pipeline("tiny/solar").c.stack is solar

    def moved(family, key):
        return after[family].get(key, 0) - before[family].get(key, 0)

    want = solar.job_counts(CFG, 19, 2, 16, 16, 64)
    assert moved("chiaswarm_text_tokens_total", "prefill") == 19
    assert moved("chiaswarm_moe_layer_steps_total", "") == 15 * 6
    seen = "chiaswarm_text_attention_pairs_total"
    assert (moved(seen, "prefill"), moved(seen, "decode")) \
        == want["attention_pairs"]
    steps = "chiaswarm_text_prefill_block_steps_total"
    assert {kind: moved(steps, kind) for kind in want["block_steps"]} \
        == want["block_steps"]
    kda = "chiaswarm_text_kda_blocks_total"
    assert (moved(kda, "pairwise"), moved(kda, "product")) \
        == want["kda_blocks"] == (4 * 2, 0)
    state = "chiaswarm_text_recurrent_state_bytes_total"
    assert {phase: moved(state, phase) for phase in ("prefill", "decode")} \
        == want["recurrent_state_bytes"]
    assert want["recurrent_state_bytes"]["decode"] > 0
    bytes_ = after["chiaswarm_text_cache_bytes"]
    assert (bytes_["recurrent"], bytes_["full"]) == (
        4 * 2 * (2 * 8 * 8 * 4 + 3 * 3 * 16 * 4),
        2 * (64 + 2 * 16) * 256)


def test_the_smoke_job_of_the_fourth_stack_settles():
    """``python -m chiaswarm_tpu.node.smoke --workflow txt2txt_solar``:
    the hard-coded job through the real dispatch path, the stack named by
    the smoke registry's catalog entry."""
    from chiaswarm_tpu.node.smoke import run_smoke

    result = run_smoke("txt2txt_solar")
    assert "error" not in result["pipeline_config"], result
    payload = json.loads(base64.b64decode(
        result["artifacts"]["primary"]["blob"]))
    assert len(payload["sequences"]) == 2
    assert all(len(s["token_logprobs"]) == 4 for s in payload["sequences"])
