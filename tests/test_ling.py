"""The Ling-3.0-flash-class decoder (models/ling.py) against the tests'
plain float32 reference (tests/ling_reference.py) on seeded weights at
the tiny size: hidden 64, 2 dense + 6 expert layers (KDA x 7, MLA x 1),
16 experts in 4 groups of which 4 are held, float32 weights.

Tolerances. Program and reference compute the same function in float32
in another order (chunks, absorbed products, grouped experts), so they
differ by rounding: logits of magnitude ~3 agree to a few 1e-6. The
limits sit a decade above that, and each test shows that the same
computation with bfloat16-rounded operands (relative step 2^-8) misses
its limit by a wide margin: a lower precision cannot hide inside them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models import ling

import ling_reference as ref

CFG = ling.LING_TINY
LOGIT_TOL = 5e-5      # |logit| ~ 3: a few float32 roundings, ~10x room
LAYER_TOL = 2e-5      # one layer's output, magnitude ~1


def bf16(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


@pytest.fixture(scope="module")
def params():
    return ling.random_params(CFG, seed=3)


@pytest.fixture(scope="module")
def sizes():
    return ref.sizes_of(CFG)


def kda_operands(seed, t, strong_decay=False, h=None, d=None):
    rng = np.random.RandomState(seed)
    h, d = h or CFG.num_attention_heads, d or CFG.head_dim
    q, k, v = (jnp.asarray(rng.randn(1, t, h, d), jnp.float32)
               for _ in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    low = -5.0 if strong_decay else -0.5
    g = jnp.asarray(rng.uniform(low, 0.0, (1, t, h, d)), jnp.float32)
    b = jnp.asarray(rng.uniform(0.0, 1.0, (1, t, h)), jnp.float32)
    state = jnp.asarray(rng.randn(1, h, d, d), jnp.float32)
    return q, k, v, g, b, state


@pytest.mark.parametrize("chunk, t", [(4, 16), (32, 128), (64, 128)],
                         ids=["chunk4", "chunk32", "chunk64"])
@pytest.mark.parametrize("strong_decay", [False, True])
def test_chunked_kda_is_the_recurrence(strong_decay, chunk, t):
    """UT-form chunks against the token-by-token recurrence of the
    reference AND of the program's own decode step. Chunks of 4 are one
    pairwise sub-block each; chunks of 32 and 64 are 2 and 4 row blocks
    of 16, whose sub-blocks left of the diagonal are products of factors
    rescaled to the row block's first row. With every gate drawn down to
    e^-5 a 64-token chunk's running decay reaches e^-320, which a ratio
    against the chunk's first or last row would lose; a ratio against the
    row block's first row is safe, because for a key before that row both
    exponents are <= 0: a factor can underflow (its weight is then under
    e^-87) and none can overflow. Every output is finite."""
    q, k, v, g, b, state = kda_operands(5, t, strong_decay, h=2, d=8)
    o, s = ling.kda_chunked(q, k, v, g, b, state, chunk=chunk)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(s)).all()
    with jax.default_matmul_precision("highest"):
        want_o, want_s = ref.kda_recurrence(q[0], k[0], v[0], g[0], b[0],
                                            state[0])
    assert np.abs(np.asarray(o[0]) - want_o).max() < LAYER_TOL
    assert np.abs(np.asarray(s[0]) - want_s).max() < LAYER_TOL
    step = jax.jit(ling.kda_recurrent_step)
    step_s, outs = state, []
    for i in range(t):
        out, step_s = step(q[:, i], k[:, i], v[:, i], g[:, i], b[:, i],
                           step_s)
        outs.append(out)
    assert np.abs(np.asarray(jnp.stack(outs, 1)) - want_o).max() < LAYER_TOL
    # bfloat16 operands miss the limit
    o16, _ = ling.kda_chunked(*bf16((q, k, v)), g, b, state, chunk=chunk)
    assert np.abs(np.asarray(o16[0]) - want_o).max() > 10 * LAYER_TOL


def _float32_sizes(jaxpr):
    """Element counts of every float32 value a jaxpr computes, its
    sub-jaxprs (the scan's body) included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if getattr(var.aval, "dtype", None) == jnp.float32:
                yield var.aval.size
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _float32_sizes(sub)


@pytest.mark.parametrize("chunk, whole", [(64, False), (4, True)],
                         ids=["chunk64-blocks-of-16", "chunk4-one-block"])
def test_chunked_kda_holds_no_chunk_by_chunk_by_channel_tensor(chunk, whole):
    """At chunk 64 the largest float32 intermediate is the diagonal
    sub-blocks' (C/16, 16, 16, D) a head, a quarter of the (C, C, D)
    that pairwise decays over the whole chunk took; at chunk 4 the one
    sub-block IS the chunk, today's mathematics."""
    h, d = 2, 8
    q, k, v, g, b, state = kda_operands(5, 2 * chunk, h=h, d=d)
    jaxpr = jax.make_jaxpr(
        lambda *a: ling.kda_chunked(*a, chunk=chunk))(q, k, v, g, b, state)
    largest = max(_float32_sizes(jaxpr.jaxpr))
    assert (largest >= h * chunk * chunk * d) == whole
    if not whole:
        assert largest == h * chunk * ling.kda_block(chunk) * d


@pytest.mark.parametrize("chunk, block", [(4, 4), (16, 16), (24, 24),
                                          (32, 16), (64, 16)])
def test_kda_block_is_16_where_16_divides_the_chunk(chunk, block):
    assert ling.kda_block(chunk) == block


@pytest.mark.parametrize("cfg, prompt, chunk, pairwise, product", [
    (CFG, 1, 8, 7 * 2, 0), (CFG, 20, 8, 7 * 3 * 2, 0),
    (dataclasses.replace(CFG, kda_chunk=64), 1, 2048, 7 * 32 * 4,
     7 * 32 * 6),
    (dataclasses.replace(CFG, kda_chunk=64), 2048, 2048, 7 * 32 * 4,
     7 * 32 * 6),
    (dataclasses.replace(CFG, kda_chunk=64), 16384, 2048, 7168, 10752),
    (dataclasses.replace(CFG, kda_chunk=64), 100, 32, 7 * 4 * 2, 7 * 4)],
    ids=["chunk4-one-token", "chunk4-three-chunks", "one-token",
         "2048-tokens", "16384-tokens-the-cell", "a-32-token-chunk"])
def test_kda_blocks_counts_sub_blocks_by_form(cfg, prompt, chunk, pairwise,
                                              product):
    """Seven KDA layers; a padded prefill chunk counts like a whole one,
    for it computes as much. Sub-chunks of 4 are one pairwise block each;
    of 64, 4 diagonal blocks and 6 left of them; of 32 (the prefill
    chunk under ``kda_chunk``), 2 and 1."""
    assert ling.kda_blocks(cfg, prompt, chunk) == (pairwise, product)


def prefill(params, cfg, ids, chunk, capacity=64):
    caches = ling.empty_prefill_caches(cfg, capacity)
    fn = jax.jit(lambda p, i, c, pos, n: ling.prefill_chunk(
        p, cfg, i, c, pos, n))
    for pos in range(0, len(ids), chunk):
        part = np.zeros((1, chunk), np.int32)
        n = min(chunk, len(ids) - pos)
        part[0, :n] = ids[pos:pos + n]
        logits, caches, stats = fn(params, jnp.asarray(part), caches,
                                   jnp.int32(pos), jnp.int32(n))
    return logits, caches, stats


def test_prefill_then_cached_decode_is_the_full_forward(params, sizes):
    """21 prompt tokens in chunks of 8 (the last one part padding), then
    6 teacher-forced tokens on two rows through both caches: every
    position's logits against one uncached pass of the reference."""
    rng = np.random.RandomState(0)
    n_prompt, n_new = 21, 6
    ids = rng.randint(0, CFG.vocab_size, n_prompt + n_new)
    want = np.asarray(ref.forward(params, sizes, ids))
    logits, caches, _ = prefill(params, CFG, ids[:n_prompt], chunk=8)
    assert np.abs(np.asarray(logits[0]) - want[n_prompt - 1]).max() \
        < LOGIT_TOL
    caches = ling.decode_caches(CFG, caches, 2, n_new)
    step = jax.jit(lambda p, t, c, n, s: ling.decode_step(p, CFG, t, c, n, s))
    for t in range(n_new):
        token = jnp.asarray([ids[n_prompt + t]] * 2, jnp.int32)
        logits, caches, _ = step(params, token, caches,
                                 jnp.int32(n_prompt), jnp.int32(t))
        for row in range(2):
            assert np.abs(np.asarray(logits[row])
                          - want[n_prompt + t]).max() < LOGIT_TOL
    # the same pass with bfloat16-rounded weights misses the limit
    logits16, _, _ = prefill(bf16(params), CFG, ids[:n_prompt], chunk=8)
    assert np.abs(np.asarray(logits16[0]) - want[n_prompt - 1]).max() \
        > 10 * LOGIT_TOL


@pytest.mark.parametrize("n, whole, padded", [(8, 8, 16), (20, 4, 8)],
                         ids=["one-chunk", "third-chunk-at-16"])
def test_a_whole_chunk_and_a_padded_one_leave_the_same_caches(
        params, n, whole, padded):
    """Padding past ``n_valid`` writes no state: ``n`` tokens in whole
    chunks against the same tokens in chunks whose last one is part
    padding (8 in a chunk of 16; 20 in chunks of 8, the padded one at
    position 16 behind two that the causal kernel reads again)."""
    ids = np.random.RandomState(1).randint(0, CFG.vocab_size, n)
    la, ca, _ = prefill(params, CFG, ids, chunk=whole)
    lb, cb, _ = prefill(params, CFG, ids, chunk=padded)
    assert np.abs(np.asarray(la) - np.asarray(lb)).max() < LOGIT_TOL
    for a, b in zip(jax.tree.leaves(ca["kda"]), jax.tree.leaves(cb["kda"])):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < LAYER_TOL
    assert np.abs(np.asarray(ca["mla"][0][:, :n])
                  - np.asarray(cb["mla"][0][:, :n])).max() < LAYER_TOL


def test_absorbed_mla_is_the_up_projected_mla(params, sizes):
    """The decode path (key up-projection folded into the query, value
    up-projection after the softmax, latents shared and own) against the
    prefill path and against the reference's uncached layer, for the
    token that follows a 12-token prompt."""
    layer = params["layers"][CFG.mla_layers[0]]["attn"]
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, 16, CFG.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mla_layer(layer, sizes, x[0, :13]))
    cache = jnp.zeros((1, 32, CFG.latent_width), jnp.float32)
    y_pre, cache = ling.mla_prefill(layer, CFG, x[:, :12], cache, 0)
    assert np.abs(np.asarray(y_pre[0]) - want[:12]).max() < LAYER_TOL
    suffix = jnp.zeros((3, 4, CFG.latent_width), jnp.float32)
    y, suffix = ling.mla_decode(
        layer, CFG, jnp.broadcast_to(x[:, 12:13], (3, 1, CFG.hidden_size)),
        cache, jnp.int32(12), suffix, jnp.int32(0))
    assert np.abs(np.asarray(y[:, 0]) - want[12]).max() < LAYER_TOL
    # and through the prefill path at offset 12, one 4-token chunk
    y_chunk, _ = ling.mla_prefill(layer, CFG, x[:, 12:16], cache, 12)
    assert np.abs(np.asarray(y_chunk[0, 0]) - want[12]).max() < LAYER_TOL
    y16, _ = ling.mla_decode(
        bf16(layer), CFG, jnp.broadcast_to(x[:, 12:13],
                                           (3, 1, CFG.hidden_size)),
        cache, jnp.int32(12), jnp.zeros_like(suffix), jnp.int32(0))
    assert np.abs(np.asarray(y16[:, 0]) - want[12]).max() > 10 * LAYER_TOL


def test_the_four_expert_shares_add_up_to_the_uncut_layer(sizes):
    """Four chips of four experts each: the parts their held experts
    give, with the shared expert (which every chip computes alike)
    counted once, are the whole layer of the uncut reference."""
    whole = dataclasses.replace(CFG, experts_held=(0, CFG.num_experts))
    layer = ling.random_params(whole, seed=4)["layers"][3]["mlp"]
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
        y, stats = ling.moe(share, cfg, x)
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
    # a layer that skipped its largest share would miss by far more
    assert np.abs(total - want).max() > 100 * LAYER_TOL


def test_the_router_keeps_its_groups_and_its_weights_sum(sizes):
    layer = ling.random_params(CFG, seed=4)["layers"][2]["mlp"]
    x = jnp.asarray(np.random.RandomState(7).randn(40, CFG.hidden_size),
                    jnp.float32)
    chosen, weight = ling.route(layer, CFG, x)
    want_chosen, want_weight = ref.route(layer, sizes, x)
    assert np.array_equal(np.sort(np.asarray(chosen), -1),
                          np.sort(want_chosen, -1))
    per_group = CFG.num_experts // CFG.n_group
    groups = np.asarray(chosen) // per_group
    assert all(len(set(row)) <= CFG.topk_group for row in groups)
    assert np.allclose(np.asarray(weight).sum(-1),
                       CFG.routed_scaling_factor, atol=1e-5)
    assert np.allclose(np.sort(np.asarray(weight), -1),
                       np.sort(want_weight, -1), atol=1e-6)


def test_causal_attention_option_matches_the_masked_einsum():
    """ops.attention(causal=True, q_offset=...) with keys wider than
    values, against a plain masked softmax."""
    from chiaswarm_tpu.ops.attention import attention

    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 8, 2, 24), jnp.float32)
    k = jnp.asarray(rng.randn(1, 20, 2, 24), jnp.float32)
    v = jnp.asarray(rng.randn(1, 20, 2, 16), jnp.float32)
    got = attention(q, k, v, causal=True, q_offset=jnp.int32(5))
    scores = jnp.einsum("blhd,bshd->bhls", q, k) * 24 ** -0.5
    visible = np.arange(20)[None, :] <= (5 + np.arange(8))[:, None]
    want = jnp.einsum("bhls,bshd->blhd", jax.nn.softmax(
        jnp.where(visible, scores, -jnp.inf), -1), v)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    # the causal flash kernel is the one path: named, it is the same call,
    # and an ``impl`` that masks nothing causally is refused
    named = attention(q, k, v, causal=True, q_offset=jnp.int32(5),
                      impl="flash")
    assert np.array_equal(np.asarray(named), np.asarray(got))
    for impl in ("xla", "ring", "ring_flash"):
        with pytest.raises(ValueError, match="no causal mask"):
            attention(q, k, v, causal=True, impl=impl)
    # the option off is the call as it was
    plain = attention(q, k[..., :24], jnp.pad(v, ((0, 0),) * 3 + ((0, 8),)))
    assert plain.shape == (1, 8, 2, 24)


def causal_operands(seed, l, s, shared, dk=24, dv=16, r=8):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, l, 2, dk), jnp.float32)
    k = jnp.asarray(rng.randn(1, s, 2, dk), jnp.float32)
    v = jnp.asarray(rng.randn(1, s, 2, dv), jnp.float32)
    pair = (jnp.asarray(rng.randn(1, l, 2, r), jnp.float32),
            jnp.asarray(rng.randn(1, s, r), jnp.float32)) if shared else None
    return q, k, v, pair


def dense_causal(q, k, v, q_offset, shared=None):
    """The plain masked softmax over every slot, in float32."""
    scores = jnp.einsum("blhd,bshd->bhls", q, k, precision=ling.HIGHEST)
    width = q.shape[-1]
    if shared is not None:
        scores = scores + jnp.einsum("blhr,bsr->bhls", *shared,
                                     precision=ling.HIGHEST)
        width += shared[0].shape[-1]
    visible = np.arange(k.shape[1])[None, :] \
        <= (q_offset + np.arange(q.shape[1]))[:, None]
    weights = jax.nn.softmax(
        jnp.where(visible, scores * width ** -0.5, -jnp.inf), -1)
    return np.asarray(jnp.einsum("bhls,bshd->blhd", weights, v,
                                 precision=ling.HIGHEST))


#: (queries, capacity, q_offset, block_q, block_kv): blocks None = the
#: kernel's own pick through ``ops.attention``
CAUSAL_CASES = {
    "first-chunk": (8, 64, 0, None, None),
    "one-block": (16, 16, 0, None, None),
    "mid-cache": (8, 64, 24, None, None),
    "last-chunk": (8, 64, 56, None, None),
    "mid-cache-two-query-blocks": (16, 64, 16, 8, 8),
    "last-chunk-wide-key-blocks": (16, 64, 48, 8, 16),
    "off-the-block-grid": (8, 40, 13, None, None),
}


def run_causal(q, k, v, shared, q_offset, block_q, block_kv):
    from chiaswarm_tpu.ops.attention import attention
    from chiaswarm_tpu.ops.causal_flash_attention import (
        causal_flash_attention,
    )

    if block_q is None:
        return np.asarray(attention(q, k, v, causal=True,
                                    q_offset=jnp.int32(q_offset),
                                    shared_key=shared))
    return np.asarray(causal_flash_attention(
        q, k, v, jnp.int32(q_offset), shared, block_q=block_q,
        block_kv=block_kv, interpret=True))


@pytest.mark.parametrize("case", CAUSAL_CASES)
def test_causal_kernel_is_the_dense_masked_einsum(case):
    """The key-blocked running softmax against the masked softmax over
    every slot, float32, at the first chunk, a cache of one block, the
    middle of the cache and its last chunk; 0.02 of a key's logit moved
    (what bfloat16 operands do) misses the limit."""
    l, s, q_offset, block_q, block_kv = CAUSAL_CASES[case]
    q, k, v, _ = causal_operands(11, l, s, shared=False)
    got = run_causal(q, k, v, None, q_offset, block_q, block_kv)
    want = dense_causal(q, k, v, q_offset)
    assert np.abs(got - want).max() < 1e-5
    got16 = run_causal(*bf16((q, k, v)), None, q_offset, block_q, block_kv)
    assert np.abs(got16 - want).max() > 1e-4


@pytest.mark.parametrize("shared", [False, True],
                         ids=["per-head-keys", "with-a-shared-key-part"])
@pytest.mark.parametrize("dk, dv", [(24, 16), (16, 24)],
                         ids=["keys-wider", "values-wider"])
def test_causal_kernel_takes_keys_and_values_of_two_widths(dk, dv, shared):
    """192 / 128 scaled down to 24 / 16 (and the other way round), with
    and without the 8-wide key part that every head shares."""
    q, k, v, pair = causal_operands(12, 16, 48, shared, dk=dk, dv=dv)
    got = run_causal(q, k, v, pair, 16, 8, 8)
    assert got.shape == (1, 16, 2, dv)
    assert np.abs(got - dense_causal(q, k, v, 16, pair)).max() < 1e-5
    # and the shared part is in the logits: left out, the answer moves
    if shared:
        alone = run_causal(q, k, v, None, 16, 8, 8)
        assert np.abs(alone - got).max() > 1e-2


@pytest.mark.parametrize("case", [c for c in CAUSAL_CASES
                                  if c != "off-the-block-grid"])
def test_causal_kernel_reads_nothing_past_the_written_cache(case):
    """Every slot from ``q_offset + L`` on holds NaN, in keys, values and
    the shared key part: a block that was read and masked would still
    put 0 x NaN into the accumulator, so a finite answer equal to the
    clean one shows those blocks are not read."""
    l, s, q_offset, block_q, block_kv = CAUSAL_CASES[case]
    q, k, v, pair = causal_operands(13, l, s, shared=True)
    end = q_offset + l
    dirty = [x.at[:, end:].set(jnp.nan) for x in (k, v, pair[1])]
    got = run_causal(q, dirty[0], dirty[1], (pair[0], dirty[2]), q_offset,
                     block_q, block_kv)
    assert np.isfinite(got).all()
    assert np.abs(got - dense_causal(q, k, v, q_offset, pair)).max() < 1e-5


@pytest.mark.parametrize("pos", [0, 8, 24, 56])
def test_mla_prefill_reads_no_latent_past_its_chunk(params, pos):
    """The layer itself: latents past ``pos + T`` are NaN and neither
    the up-projection nor the kernel touches them; the chunk's output is
    the one a clean cache gives, and the cache comes back with the
    chunk's entries written and the NaN where it was."""
    layer = params["layers"][CFG.mla_layers[0]]["attn"]
    rng = np.random.RandomState(14)
    x = jnp.asarray(rng.randn(1, 8, CFG.hidden_size), jnp.float32)
    clean = jnp.asarray(rng.randn(1, 64, CFG.latent_width), jnp.float32)
    clean = clean.at[:, pos:].set(0.0)
    want, want_cache = ling.mla_prefill(layer, CFG, x, clean, pos)
    dirty = clean.at[:, pos + 8:].set(jnp.nan)
    got, cache = jax.jit(
        lambda c, p: ling.mla_prefill(layer, CFG, x, c, p))(
            dirty, jnp.int32(pos))
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < LAYER_TOL
    assert np.abs(np.asarray(cache[:, :pos + 8])
                  - np.asarray(want_cache[:, :pos + 8])).max() < LAYER_TOL
    assert np.isnan(np.asarray(cache[:, pos + 8:])).all()


@pytest.mark.parametrize("prompt, read, of", [
    (8, 1, 8), (20, 6, 24), (64, 36, 64)],
    ids=["one-chunk-12.5%", "three-chunks", "the-whole-context-56%"])
def test_prefill_key_blocks_counts_what_the_bound_admits(prompt, read, of):
    """Chunks of 8 against a capacity of 64 (one latent-attention layer,
    a key block = a chunk): chunk i reads i + 1 of 8 blocks."""
    assert ling.prefill_key_blocks(CFG, prompt, 8, 64) == (read, of)


def test_the_layout_is_the_published_pattern():
    cfg = ling.LingConfig(num_hidden_layers=8, vocab_size=39296,
                          experts_held=(0, 128))
    assert cfg.mla_layers == [5] and len(cfg.kda_layers) == 7
    shapes = ling.param_shapes(cfg)
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    # 6 x 128 experts of 3 x 2560 x 768, ~0.61 B outside them, 0.20 B of
    # embedding and head: 10.7 GB in bfloat16
    assert 5.3e9 < count < 5.4e9
    experts = shapes["layers"][2]["mlp"]["experts"]
    assert experts["gate"].shape == (128, 2560, 768)
    assert shapes["layers"][2]["mlp"]["router"].shape == (2560, 512)
    assert shapes["layers"][5]["attn"]["wdkv"].shape == (2560, 576)
    assert "experts" not in shapes["layers"][1]["mlp"]
