"""The latent-attention decode keeps its scores on the chip (ISSUE 34):
the prompt's part of ``text_layers.latent_decode`` is one key-blocked
sweep (``ops.attention.shared_latent_attention``: the causal kernel with
every row at one position, Pallas interpret mode here), joined to the
rows' own suffixes by the log-sum-exps.

- the sweep against the dense masked softmax at a full cache, inside a
  block, on a block's edge and at one key; rows that do not fill a
  block; both widths of latent; NaN past the prompt;
- ``latent_decode`` against the parent's dense form (kept here as the
  reference) for both stacks' tiny configurations at the first, a middle
  and the last step;
- no (rows, heads, capacity) array in the decode's program;
- the counter from host integers;
- the prefill's latent form: ``latent_prefill`` against the
  parent's unfused form (kept here) for both stacks, in float32 and in
  bfloat16; no (1, S, H x D) keys or values and no loop in its program at
  the DeepSeek cell's widths; its up-projections counted from host
  integers against a brute-force count of the grid.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.models import deepseek, ling, text_layers
from chiaswarm_tpu.ops.attention import shared_latent_attention
from chiaswarm_tpu.ops.causal_flash_attention import (
    shared_key_block,
    shared_latent_attention as sweep,
)

HIGHEST = jax.lax.Precision.HIGHEST


def bf16(tree):
    return jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                        if x.dtype == jnp.float32 else x, tree)


# ---- the sweep -------------------------------------------------------------


def operands(seed, n, s, rank=16, rotary=8):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, rank + rotary), jnp.float32),
            jnp.asarray(rng.randn(s, rank + rotary), jnp.float32))


def dense(q, keys, n_keys, rank):
    """The plain masked softmax over every slot, float32: (read-out,
    log-sum-exp)."""
    scores = jnp.einsum("nw,sw->ns", q, keys, precision=HIGHEST) \
        * q.shape[1] ** -0.5
    scores = jnp.where(jnp.arange(keys.shape[0]) < n_keys, scores, -jnp.inf)
    out = jnp.einsum("ns,sc->nc", jax.nn.softmax(scores, -1),
                     keys[:, :rank], precision=HIGHEST)
    return np.asarray(out), np.asarray(jax.nn.logsumexp(scores, -1))


#: (rows, capacity, visible keys, block_q, block_kv): blocks None = the
#: kernel's own pick through ``ops.attention``
SWEEP_CASES = {
    "the-capacity": (8, 64, 64, None, None),
    "the-capacity-in-blocks": (16, 64, 64, 8, 16),
    "inside-a-block": (16, 64, 37, 8, 16),
    "on-a-blocks-edge": (16, 64, 32, 8, 16),
    "one-key": (16, 64, 1, 8, 16),
    "rows-that-do-not-fill-a-block": (20, 64, 37, 8, 16),
    "rows-that-do-not-fill-their-one-block": (6, 48, 29, None, None),
}


def run_sweep(q, keys, n_keys, rank, block_q, block_kv):
    if block_q is None:
        got = shared_latent_attention(q, keys, jnp.int32(n_keys),
                                      value_width=rank)
    else:
        got = sweep(q, keys, jnp.int32(n_keys), value_width=rank,
                    block_q=block_q, block_kv=block_kv, interpret=True)
    return tuple(np.asarray(x) for x in got)


@pytest.mark.parametrize("case", SWEEP_CASES)
def test_the_sweep_is_the_dense_masked_softmax(case):
    """Read-out and log-sum-exp against the masked softmax over every
    slot, float32; 0.02 of a logit moved (what bfloat16 operands do)
    misses the limit."""
    n, s, n_keys, block_q, block_kv = SWEEP_CASES[case]
    q, keys = operands(21, n, s)
    out, lse = run_sweep(q, keys, n_keys, 16, block_q, block_kv)
    want_out, want_lse = dense(q, keys, n_keys, 16)
    assert out.shape == (n, 16) and out.dtype == np.float32
    assert lse.shape == (n,) and lse.dtype == np.float32
    assert np.abs(out - want_out).max() < 1e-5
    assert np.abs(lse - want_lse).max() < 1e-5
    out16, _ = run_sweep(*bf16((q, keys)), n_keys, 16, block_q, block_kv)
    assert np.abs(out16 - want_out).max() > 1e-4


@pytest.mark.parametrize("rank, rotary", [(16, 8), (128, 64), (128, 8)],
                         ids=["a-padded-copy-of-the-latents",
                              "the-latents-where-they-lie",
                              "where-they-lie-narrow-rotary"])
def test_the_sweep_takes_both_widths_with_the_shared_rotary_part(rank,
                                                                 rotary):
    """512 + 64 scaled down: a latent that fills whole lane tiles is
    read out of the cache as it lies (no copy), a narrower one from a
    padded copy; the rotary columns are in the logits and not in the
    values. Either way what lies past the prompt (NaN here) and the lanes
    past the cache's width change nothing."""
    q, keys = operands(22, 16, 64, rank, rotary)
    out, lse = run_sweep(q, keys.at[41:].set(jnp.nan), 41, rank, 8, 16)
    want_out, want_lse = dense(q, keys, 41, rank)
    assert out.shape == (16, rank)
    assert np.abs(out - want_out).max() < 1e-5
    assert np.abs(lse - want_lse).max() < 1e-5
    # the rotary part is in the logits: zeroed, the answer moves
    alone, _ = run_sweep(q.at[:, rank:].set(0.0), keys, 41, rank, 8, 16)
    assert np.abs(alone - out).max() > 1e-2


@pytest.mark.parametrize("case", [c for c in SWEEP_CASES
                                  if c != "the-capacity"])
def test_the_sweep_is_blind_to_what_lies_past_the_prompt(case):
    """Every slot from ``n_keys`` on holds NaN. Blocks past the prompt
    are not read; in the block that holds its end the keys are masked
    and the values zeroed (0 x NaN would reach the accumulator), so the
    answer is the clean one."""
    n, s, n_keys, block_q, block_kv = SWEEP_CASES[case]
    q, keys = operands(23, n, s)
    dirty = keys.at[n_keys:].set(jnp.nan)
    out, lse = run_sweep(q, dirty, n_keys, 16, block_q, block_kv)
    want_out, want_lse = dense(q, keys, n_keys, 16)
    assert np.isfinite(out).all() and np.isfinite(lse).all()
    assert np.abs(out - want_out).max() < 1e-5
    assert np.abs(lse - want_lse).max() < 1e-5


def test_the_decodes_block_follows_the_capacity():
    """Whole blocks at the served capacity (no padded copy of the cache a
    step), the 8-padded capacity itself where that is smaller."""
    assert 16384 % shared_key_block(16384) == 0
    assert 128 <= shared_key_block(16384) <= 2048
    assert shared_key_block(40) == 40 and shared_key_block(5) == 8


# ---- the layer against the parent's dense form -----------------------------


def dense_latent_decode(p, cfg, x, q, prompt_cache, prompt_len, suffix, step,
                        *, inv_freq, scale, rope_amplitude=1.0):
    """``text_layers.latent_decode`` as it stood before ISSUE 34: the
    (R, H, S) scores in memory, prompt and suffix in one softmax."""
    rank = cfg.kv_lora_rank
    position = (prompt_len + step)[None]
    q_n, q_r, entry = text_layers._query_and_entry(
        p, cfg, x, q, position, inv_freq, rope_amplitude)
    suffix = jax.lax.dynamic_update_slice_in_dim(suffix, entry, step, axis=1)
    w_uk, w_uv = text_layers._up_projections(p, cfg)
    q_abs = jnp.einsum("rhd,chd->rhc", q_n[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    q_all = jnp.concatenate([q_abs, q_r[:, 0]], axis=-1)
    shared = prompt_cache[0]
    s_prompt = jnp.einsum("rhw,sw->rhs", q_all, shared,
                          preferred_element_type=jnp.float32)
    s_own = jnp.einsum("rhw,rnw->rhn", q_all, suffix,
                       preferred_element_type=jnp.float32)
    s_prompt = jnp.where(jnp.arange(shared.shape[0]) < prompt_len,
                         s_prompt, text_layers.NEG_INF)
    s_own = jnp.where(jnp.arange(suffix.shape[1]) <= step, s_own,
                      text_layers.NEG_INF)
    weights = jax.nn.softmax(
        jnp.concatenate([s_prompt, s_own], -1) * scale, axis=-1)
    weights = weights.astype(x.dtype)
    n_prompt = shared.shape[0]
    o_lat = jnp.einsum("rhs,sc->rhc", weights[..., :n_prompt],
                       shared[:, :rank],
                       preferred_element_type=jnp.float32) \
        + jnp.einsum("rhn,rnc->rhc", weights[..., n_prompt:],
                     suffix[..., :rank], preferred_element_type=jnp.float32)
    o = jnp.einsum("rhc,chd->rhd", o_lat.astype(x.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    return o[:, None].astype(x.dtype), suffix


STACKS = {"ling": (ling, ling.LING_TINY, ling.LING_TINY.mla_layers[0]),
          "deepseek": (deepseek, deepseek.TINY, 2)}
ROWS, CAPACITY, MAX_NEW = 3, 48, 8


def layer_operands(stack, seed, prompt_len, dtype=jnp.float32):
    module, cfg, index = STACKS[stack]
    layer = module.random_params(cfg, seed=4)["layers"][index]["attn"]
    rng = np.random.RandomState(seed)
    width = text_layers.latent_width(cfg)
    x = jnp.asarray(rng.randn(ROWS, 1, cfg.hidden_size), jnp.float32)
    cache = jnp.asarray(rng.randn(1, CAPACITY, width), jnp.float32)
    cache = cache.at[:, prompt_len:].set(0.0)
    suffix = jnp.asarray(rng.randn(ROWS, MAX_NEW, width), jnp.float32)
    cast = (lambda t: t) if dtype == jnp.float32 else bf16
    return module, cfg, cast(layer), cast(x), cast(cache), cast(suffix)


def both_forms(monkeypatch, module, cfg, layer, x, cache, prompt_len,
               suffix, step):
    args = (layer, cfg, x, cache, jnp.int32(prompt_len), suffix,
            jnp.int32(step))
    got = module.mla_decode(*args)
    with monkeypatch.context() as patch:
        patch.setattr(text_layers, "latent_decode", dense_latent_decode)
        want = module.mla_decode(*args)
    return [tuple(np.asarray(a, np.float32) for a in pair)
            for pair in (got, want)]


@pytest.mark.parametrize("step", [0, 3, MAX_NEW - 1],
                         ids=["first-step", "mid-way", "last-step"])
@pytest.mark.parametrize("prompt_len", [CAPACITY, 13],
                         ids=["a-full-cache", "a-short-prompt"])
@pytest.mark.parametrize("stack", STACKS)
def test_latent_decode_is_the_parents_dense_form(monkeypatch, stack,
                                                 prompt_len, step):
    """The layer's output and suffix through the sweep and the merge
    against the one softmax over prompt + suffix, float32: rounding; the
    suffix left out of the merge would miss by far more."""
    module, cfg, layer, x, cache, suffix = layer_operands(
        stack, 31, prompt_len)
    (y, new_suffix), (want, want_suffix) = both_forms(
        monkeypatch, module, cfg, layer, x, cache, prompt_len, suffix, step)
    assert y.shape == (ROWS, 1, cfg.hidden_size)
    assert np.abs(y - want).max() < 1e-5 * max(1.0, np.abs(want).max())
    assert np.array_equal(new_suffix, want_suffix)
    (alone, _), _ = both_forms(
        monkeypatch, module, cfg, layer, x, cache, prompt_len,
        jnp.zeros_like(suffix), step)
    if step:
        assert np.abs(alone - want).max() > 1e-3


@pytest.mark.parametrize("stack", STACKS)
def test_latent_decode_in_bfloat16_stays_as_close_as_the_parent(monkeypatch,
                                                                stack):
    """Served dtype: both forms round the probabilities to bfloat16
    before the read-out and sum in float32; against the float32 layer
    the sweep misses by no more than 1.5 times what the parent does."""
    module, cfg, layer, x, cache, suffix = layer_operands(stack, 32, 37)
    _, (exact, _) = both_forms(monkeypatch, module, cfg, layer, x, cache,
                               37, suffix, 3)
    module, cfg, layer, x, cache, suffix = layer_operands(
        stack, 32, 37, jnp.bfloat16)
    (y, _), (want, _) = both_forms(monkeypatch, module, cfg, layer, x,
                                   cache, 37, suffix, 3)
    assert np.abs(y - exact).max() < 1.5 * np.abs(want - exact).max()


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


def _scores_sized(shapes, heads):
    """Shapes with as many elements as rows x heads x capacity that end
    in the capacity (with or without the suffix beside it)."""
    return [s for s in shapes
            if s and s[-1] in (CAPACITY, CAPACITY + MAX_NEW)
            and np.prod(s) >= ROWS * heads * CAPACITY]


@pytest.mark.parametrize("stack", STACKS)
def test_no_rows_by_heads_by_capacity_array_is_left_in_the_decode(
        monkeypatch, stack):
    """The whole decode step of a stack (every layer, the scan's body as
    ``TextPipeline`` runs it) holds no array of the scores' size outside
    the kernel; the parent's form, traced the same way, does."""
    module, cfg, _ = STACKS[stack]
    params = module.random_params(cfg, seed=4)
    caches = module.decode_caches(
        cfg, module.empty_prefill_caches(cfg, CAPACITY), ROWS, MAX_NEW)

    def traced():
        def step(params, caches):     # a new function: no cached trace
            return module.decode_step(
                params, cfg, jnp.zeros((ROWS,), jnp.int32), caches,
                jnp.int32(20), jnp.int32(2))

        return jax.make_jaxpr(step)(params, caches)

    heads = cfg.num_attention_heads
    program = traced()
    assert "pallas_call" in str(program)
    assert not _scores_sized(_shapes(program.jaxpr), heads)
    monkeypatch.setattr(text_layers, "latent_decode", dense_latent_decode)
    assert (ROWS, heads, CAPACITY) in _scores_sized(
        _shapes(traced().jaxpr), heads)


# ---- the counter -----------------------------------------------------------


def test_decode_key_blocks_are_counted_from_host_integers():
    """Blocks of the prompt against blocks of the capacity, per
    latent-attention layer and decode step (``new - 1`` of them): the
    cell's job reads them all, a quarter-length prompt a quarter."""
    big = deepseek.DeepseekConfig(num_hidden_layers=5)
    block = shared_key_block(16384)
    full = deepseek.job_counts(big, 16384, 16, 64, 2048, 16384)
    assert full["decode_key_blocks"] == (315 * 16384 // block,) * 2
    quarter = deepseek.job_counts(big, 4096, 16, 64, 2048, 16384)
    assert quarter["decode_key_blocks"] == (315 * 4096 // block,
                                            315 * 16384 // block)
    # one token past a block's edge reads one block more a layer-step
    over = deepseek.job_counts(big, 4097, 16, 64, 2048, 16384)
    assert over["decode_key_blocks"][0] == 315 * (4096 // block + 1)
    # the other stack: one latent-attention layer in its tiny preset,
    # five in this one's; the tiny capacity is one block
    assert ling.job_counts(ling.LING_TINY, 19, 2, 16, 8, 32)[
        "decode_key_blocks"] == (15, 15)
    assert deepseek.job_counts(deepseek.TINY, 19, 2, 16, 8, 32)[
        "decode_key_blocks"] == (5 * 15, 5 * 15)


@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("tokens, share", [(16384, 1.0), (4096, 0.25)],
                         ids=["a-full-prompt", "a-quarter"])
def test_count_feeds_the_decode_key_blocks_family(stack, tokens, share):
    """``TextPipeline._count`` moves ``read="yes"`` by the prompt's
    blocks and ``read="no"`` by the rest, for both stacks."""
    from chiaswarm_tpu.obs.metrics import REGISTRY
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    module, cfg, _ = STACKS[stack]
    pipe = TextPipeline(TextComponents.random(cfg, seed=1),
                        prefill_chunk=2048, max_context=16384)

    def counted():
        values = REGISTRY.snapshot()[
            "chiaswarm_text_decode_key_blocks_total"]["values"]
        return np.array([values.get(k, 0) for k in ("yes", "no")])

    zero = {k: 0 for k in module.empty_stats()}
    before = counted()
    pipe._count(tokens, 16, 64, zero, zero)
    yes, no = counted() - before
    layers = len(getattr(cfg, "mla_layers", range(cfg.num_hidden_layers)))
    assert yes + no == layers * 63 * 16384 // shared_key_block(16384)
    assert yes == share * (yes + no)


# ---- the prefill kernel's grid steps, from host integers (ISSUE 36) --------


def test_the_cells_jobs_enter_no_step_past_the_written_cache():
    """The DeepSeek job (five latent layers of 128 heads, eight chunks of
    2,048 against 16,384 slots): the parent's grid of 1024 x 1024 pairs
    entered 76,800 steps with all their pairs visible, 10,240 on the
    diagonal and 76,800 with nothing to do (120 a head a layer: above
    the diagonal or past the written cache). One 2,048-row query block
    against the keys written so far enters none of the last; each of a
    chunk's two diagonal pairs is cut in 512-row tiles, 10 of whose 16
    512 x 512 sub-tiles hold a visible pair."""
    big = deepseek.DeepseekConfig(num_hidden_layers=5)
    steps = deepseek.job_counts(big, 16384, 16, 64, 2048, 16384)[
        "block_steps"]
    grid_heads = 5 * 128
    assert steps["dead"] == 0
    assert steps["whole"] == grid_heads * sum(2 * c for c in range(8))
    assert steps["diagonal"] == grid_heads * 8 * 10
    # the pairs those steps score: the keys before a chunk whole, the
    # chunk's own triangle at 10 / 16 of its square (8 / 16 are visible)
    from chiaswarm_tpu.ops.causal_flash_attention import block_steps

    scored = sum(block_steps(2048, 1, pos, 16384)["pairs"]
                 for pos in range(0, 16384, 2048))
    assert scored == sum(2048 * pos + 10 * 512 * 512
                         for pos in range(0, 16384, 2048))
    # a quarter-length prompt: two chunks
    short = deepseek.job_counts(big, 4096, 16, 64, 2048, 16384)[
        "block_steps"]
    assert short == {"whole": grid_heads * 2, "diagonal": grid_heads * 20,
                     "dead": 0}
    # the Ling job: one latent layer of 32 heads, the same eight chunks
    cell = ling.LingConfig(num_hidden_layers=8)
    assert len(cell.mla_layers) == 1 and cell.num_attention_heads == 32
    assert ling.job_counts(cell, 16384, 32, 128, 2048, 16384)[
        "block_steps"] == {"whole": 32 * 56, "diagonal": 32 * 80, "dead": 0}


@pytest.mark.parametrize("offset", [0, 64, 37, 192],
                         ids=["first", "second", "odd", "last"])
def test_block_steps_are_what_a_brute_force_count_of_the_grid_finds(offset):
    """64 positions against 256 slots in 64 x 32 blocks (the entry's pick
    for a 32-position chunk is asked of ``block_steps`` itself): every
    (query block, key block) pair up to the written cache's last block
    is whole, crossed or above the diagonal by its corner pairs."""
    from chiaswarm_tpu.ops.causal_flash_attention import (
        _prefill_blocks,
        block_steps,
    )

    positions, keys = 64, 256
    block_q, block_kv = _prefill_blocks(positions, 1, keys, None)
    got = block_steps(positions, 1, offset, keys)
    whole = crossed = dead = 0
    for i in range(0, positions, block_q):
        rows = np.arange(offset + i, offset + i + block_q)
        for j in range(0, -(-(offset + positions) // block_kv) * block_kv,
                       block_kv):
            visible = np.arange(j, j + block_kv)[None, :] <= rows[:, None]
            whole += visible.all()
            dead += not visible.any()
            crossed += visible.any() and not visible.all()
    assert (got["whole"], got["dead"]) == (whole, dead)
    # a crossed pair counts its scored sub-tiles when it is cut to the
    # diagonal (an offset on the blocks' grid), itself when masked whole
    assert got["diagonal"] >= crossed
    if offset % min(block_q, block_kv):
        assert got["diagonal"] == crossed


@pytest.mark.parametrize("stack", STACKS)
def test_count_feeds_the_block_steps_family(stack):
    """``TextPipeline._count`` moves the three kinds by the stack's
    ``job_counts``, for both stacks."""
    from chiaswarm_tpu.obs.metrics import REGISTRY
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    module, cfg, _ = STACKS[stack]
    pipe = TextPipeline(TextComponents.random(cfg, seed=1),
                        prefill_chunk=2048, max_context=16384)
    kinds = ("whole", "diagonal", "dead")

    def counted():
        values = REGISTRY.snapshot()[
            "chiaswarm_text_prefill_block_steps_total"]["values"]
        return np.array([values.get(k, 0) for k in kinds])

    zero = {k: 0 for k in module.empty_stats()}
    before = counted()
    pipe._count(16384, 16, 64, zero, zero)
    want = module.job_counts(cfg, 16384, 16, 64, 2048, 16384)["block_steps"]
    assert list(counted() - before) == [want[k] for k in kinds]
    assert want["whole"] > 0 and want["diagonal"] > 0


# ---- the latent prefill: the sweep up-projects the latents ----------------


def unfused_latent_prefill(p, cfg, x, q, cache, pos, *, inv_freq, scale,
                           rope_amplitude=1.0):
    """``text_layers.latent_prefill`` in its unfused form: the
    written latents up-projected to (B, S, H x D) keys and values in
    memory, a key block at a time in a loop over two zero-filled arrays,
    and the sweep over those."""
    from chiaswarm_tpu.ops.attention import attention
    from chiaswarm_tpu.ops.causal_flash_attention import key_block

    b_, t, _ = x.shape
    h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    q_n, q_r, entry = text_layers._query_and_entry(
        p, cfg, x, q, pos + jnp.arange(t), inv_freq, rope_amplitude)
    cache = jax.lax.dynamic_update_slice_in_dim(cache, entry, pos, axis=1)
    s = cache.shape[1]
    block = key_block(t, s)
    w_uk, w_uv = (w.reshape(rank, -1)
                  for w in text_layers._up_projections(p, cfg))

    def up_project(i, kv):
        latents = jax.lax.dynamic_slice_in_dim(
            cache, i * block, block, axis=1)[..., :rank]
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                whole, text_layers.proj(latents, w), i * block, axis=1)
            for whole, w in zip(kv, (w_uk, w_uv)))

    k_n, v = jax.lax.fori_loop(
        0, (pos + t + block - 1) // block, up_project,
        tuple(jnp.zeros((b_, s, w.shape[1]), x.dtype)
              for w in (w_uk, w_uv)))
    o = attention(q_n, k_n.reshape(b_, s, h, -1), v.reshape(b_, s, h, -1),
                  scale=scale, causal=True, q_offset=pos,
                  shared_key=(q_r, cache[..., rank:]))
    return o, cache


CHUNK = 16


def prefill_forms(monkeypatch, stack, pos, dtype=jnp.float32):
    """(the layer's output, the cache) of one chunk at ``pos`` of a
    stack's tiny latent-attention layer, through the served form and the
    parent's unfused one; the cache holds random latents before ``pos``
    and zeros after."""
    module, cfg, layer, _, cache, _ = layer_operands(stack, 33, pos, dtype)
    x = jnp.asarray(np.random.RandomState(34).randn(1, CHUNK,
                                                    cfg.hidden_size), dtype)
    fn = jax.jit(lambda c, at: module.mla_prefill(layer, cfg, x, c, at))
    got = fn(cache, jnp.int32(pos))
    with monkeypatch.context() as patch:
        patch.setattr(text_layers, "latent_prefill", unfused_latent_prefill)
        want = jax.jit(
            lambda c, at: module.mla_prefill(layer, cfg, x, c, at))(
                cache, jnp.int32(pos))
    return [tuple(np.asarray(a, np.float32) for a in pair)
            for pair in (got, want)]


@pytest.mark.parametrize("pos", [0, 16, CAPACITY - CHUNK],
                         ids=["first-chunk", "a-middle-chunk", "last-chunk"])
@pytest.mark.parametrize("stack", STACKS)
def test_latent_prefill_is_the_parents_unfused_form(monkeypatch, stack, pos):
    """A chunk of 16 tokens of each stack's tiny layer (a latent of 24,
    read through a padded copy), float32: the sweep that up-projects its
    latents gives the output that keys and values up-projected in memory
    gave, and writes the same cache."""
    (y, cache), (want, want_cache) = prefill_forms(monkeypatch, stack, pos)
    assert np.abs(y - want).max() < 1e-5 * max(1.0, np.abs(want).max())
    assert np.array_equal(cache, want_cache)


@pytest.mark.parametrize("stack", STACKS)
def test_latent_prefill_in_bfloat16_stays_as_close_as_the_parent(
        monkeypatch, stack):
    """Served dtype, the last chunk: both forms round the keys and values
    to bfloat16 from a float32 sum, so against the float32 layer the
    latent form misses by no more than 1.5 times what the parent does."""
    pos = CAPACITY - CHUNK
    _, (exact, _) = prefill_forms(monkeypatch, stack, pos)
    (y, _), (want, _) = prefill_forms(monkeypatch, stack, pos, jnp.bfloat16)
    assert np.abs(y - exact).max() < 1.5 * np.abs(want - exact).max()


def _primitives(jaxpr):
    """Names of every primitive a jaxpr runs, sub-jaxprs included, a
    Pallas kernel's body left out."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_the_latent_prefill_holds_no_keys_or_values_and_no_loop(
        monkeypatch):
    """One DeepSeek layer at the cell's widths (128 heads, a 512 + 64
    latent, a 2,048-token chunk against 16,384 slots), traced as a TPU
    process traces it: no (1, 16384, 128 x 128) keys or values and no
    loop outside the kernel; the parent's form, traced the same way,
    holds both."""
    from unittest import mock

    cfg = deepseek.DeepseekConfig(num_hidden_layers=1)
    layer = deepseek.param_shapes(cfg)["layers"][0]["attn"]
    bf = jnp.bfloat16

    def traced():
        def chunk(p, x, cache, pos):    # a new function: no cached trace
            return deepseek.mla_prefill(p, cfg, x, cache, pos)

        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return jax.make_jaxpr(chunk)(
                layer, jax.ShapeDtypeStruct((1, 2048, 5120), bf),
                jax.ShapeDtypeStruct((1, 16384, 576), bf),
                jax.ShapeDtypeStruct((), jnp.int32)).jaxpr

    keys_sized = (1, 16384, 128 * 128)
    program = traced()
    assert "pallas_call" in set(_primitives(program))
    assert "while" not in set(_primitives(program))
    assert keys_sized not in set(_shapes(program))
    monkeypatch.setattr(text_layers, "latent_prefill", unfused_latent_prefill)
    parents = traced()
    assert "while" in set(_primitives(parents))
    assert keys_sized in set(_shapes(parents))


# ---- the up-projections counted from host integers --------------------------


def brute_force_up_projections(positions, q_offset, keys):
    """The latent prefill's (query block, key block) steps a head that
    hold a visible pair, up to the written cache's last key block, found
    by looking at every pair of every block of the grid."""
    from chiaswarm_tpu.ops.causal_flash_attention import _prefill_blocks

    block_q, block_kv = _prefill_blocks(positions, 1, keys, None)
    written = min(-(-(q_offset + positions) // block_kv), -(-keys // block_kv))
    count = 0
    for i in range(0, positions, block_q):
        rows = np.arange(q_offset + i, q_offset + i + block_q)
        for j in range(0, written * block_kv, block_kv):
            count += (np.arange(j, j + block_kv)[None, :]
                      <= rows[:, None]).any()
    return count


@pytest.mark.parametrize("prompt, chunk, capacity", [
    (16384, 2048, 16384), (4096, 2048, 16384), (4097, 2048, 16384),
    (20, 8, 64), (64, 16, 64), (40, 32, 256), (7, 16, 64),
    (8192, 4096, 16384)],
    ids=["the-cells-job", "a-quarter", "a-token-more", "three-chunks",
         "the-whole-context", "a-ragged-chunk", "part-of-a-chunk",
         "two-query-blocks-a-chunk"])
def test_up_projections_are_what_a_brute_force_count_of_the_grid_finds(
        prompt, chunk, capacity):
    """Every latent layer's heads up-project, for each chunk, the key
    blocks that hold a pair some query of a block sees, as far as the
    cache is written; none is made in memory. A chunk of two query blocks
    up-projects a block once for each that reaches it."""
    want = sum(brute_force_up_projections(chunk, pos, capacity)
               for pos in range(0, prompt, chunk))
    for module, cfg in ((deepseek, deepseek.DeepseekConfig(
            num_hidden_layers=5)), (ling, ling.LingConfig(
                num_hidden_layers=8))):
        layers = len(getattr(cfg, "mla_layers", range(5)))
        assert module.job_counts(cfg, prompt, 16, 64, chunk, capacity)[
            "up_projections"] == {
                "kernel": layers * cfg.num_attention_heads * want,
                "memory": 0}


def test_the_cells_jobs_up_project_each_written_block_once_a_chunk():
    """The DeepSeek job: five layers of 128 heads, chunk c (of eight)
    up-projects its 2c key blocks of 1,024 latents, 46,080 in all; the
    Ling job's one layer of 32 heads, 2,304; the Laguna job has no latent
    layer."""
    from chiaswarm_tpu.models import laguna

    cell = deepseek.DeepseekConfig(num_hidden_layers=5)
    assert deepseek.job_counts(cell, 16384, 16, 64, 2048, 16384)[
        "up_projections"] == {"kernel": 46080, "memory": 0}
    assert 5 * 128 * sum(2 * c for c in range(1, 9)) == 46080
    assert ling.job_counts(ling.LingConfig(num_hidden_layers=8), 16384, 32,
                           128, 2048, 16384)["up_projections"] \
        == {"kernel": 2304, "memory": 0}
    assert "up_projections" not in laguna.job_counts(
        laguna.LagunaConfig(), 16384, 32, 128, 2048, 16384)


@pytest.mark.parametrize("stack", ["ling", "deepseek", "laguna"])
def test_count_feeds_the_up_projections_family(stack):
    """``TextPipeline._count`` moves ``where="kernel"`` by the stack's
    ``job_counts`` and ``where="memory"`` by nothing, for all three
    stacks (Laguna's, with no latent layer, moves neither)."""
    from chiaswarm_tpu.models import laguna
    from chiaswarm_tpu.obs.metrics import REGISTRY
    from chiaswarm_tpu.pipelines.text import TextComponents, TextPipeline

    module, cfg = {"ling": (ling, ling.LING_TINY),
                   "deepseek": (deepseek, deepseek.TINY),
                   "laguna": (laguna, laguna.TINY)}[stack]
    pipe = TextPipeline(TextComponents.random(cfg, seed=1),
                        prefill_chunk=2048, max_context=16384)

    def counted():
        values = REGISTRY.snapshot()[
            "chiaswarm_text_prefill_up_projections_total"]["values"]
        return np.array([values.get(k, 0) for k in ("kernel", "memory")])

    zero = {k: 0 for k in module.empty_stats()}
    before = counted()
    pipe._count(16384, 16, 64, zero, zero)
    kernel, memory = counted() - before
    want = module.job_counts(cfg, 16384, 16, 64, 2048, 16384).get(
        "up_projections", {"kernel": 0, "memory": 0})
    assert (kernel, memory) == (want["kernel"], want["memory"])
    assert memory == 0 and (kernel > 0) == (stack != "laguna")
