"""Kernel tests: Pallas flash attention (interpret mode on CPU) vs the
einsum reference — the golden-value strategy SURVEY.md §4 calls for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.ops.attention import _xla_attention, attention
from chiaswarm_tpu.ops.flash_attention import flash_attention


@pytest.mark.parametrize(
    "b,l,s,h,d",
    [
        (2, 64, 64, 4, 40),    # SD1.5-style self-attention head_dim 40
        (1, 100, 77, 2, 64),   # cross-attention: text KV of 77 tokens
        (1, 300, 300, 2, 80),  # non-multiple-of-block lengths
        (2, 128, 128, 1, 128), # exact lane-width head dim
    ],
)
def test_flash_matches_einsum(b, l, s, h, d):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, l, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    scale = d ** -0.5
    ref = _xla_attention(q, k, v, scale)
    got = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_bf16_io():
    kq, kk = jax.random.split(jax.random.PRNGKey(1))
    q = jax.random.normal(kq, (1, 96, 2, 32), jnp.bfloat16)
    kvv = jax.random.normal(kk, (1, 96, 2, 32), jnp.bfloat16)
    out = flash_attention(q, kvv, kvv, block_q=32, block_kv=32,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _xla_attention(q.astype(jnp.float32), kvv.astype(jnp.float32),
                         kvv.astype(jnp.float32), 32 ** -0.5)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=3e-2, atol=3e-2
    )


def test_attention_dispatch_explicit_flash():
    """impl="flash" forces the Pallas kernel even on CPU (interpret)."""
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 16))
    out_flash = attention(q, q, q, impl="flash")
    out_xla = attention(q, q, q, impl="xla")
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_xla),
                               rtol=2e-4, atol=2e-4)


def test_flash_block_autopick_divisibility():
    """The auto block picker (ops/flash_attention.py::_pick_block):
    non-divisible lengths switch to the largest tuned-subdivision block
    that removes the masked padding (the SVD portrait's +4.2%); every
    power-of-two SD/SDXL shape keeps the tuned 2048/1024 blocks
    bit-for-bit; the r2 small-block cliff (256/512) is never selected;
    sub-threshold savings stay on the tuned block."""
    from chiaswarm_tpu.ops.flash_attention import _pick_block

    # tuned shapes unchanged (SDXL 1024px levels, SD 512px levels)
    assert _pick_block(16384, 2048) == 2048
    assert _pick_block(4096, 2048) == 2048
    assert _pick_block(4096, 1024) == 1024
    # SVD portrait levels tile exactly
    assert _pick_block(9216, 2048) == 1536
    assert _pick_block(9216, 1024) == 1024
    assert _pick_block(2304, 2048) == 768
    assert _pick_block(2304, 1024) == 768
    # 256-divisible lengths must NOT fall to the small-block cliff
    assert _pick_block(12544, 2048) == 1280
    # below-threshold saving keeps the tuned block (6% vs 4% padding)
    assert _pick_block(12544, 1024) == 1024
    # short sequences clamp to the 8-padded length as before
    assert _pick_block(77, 2048) == 80
    assert _pick_block(256, 2048) == 256


# ---- cross-lowering for the TPU from the CPU (no chip needed) -----------
# ``jax.jit(f).trace(*abstract).lower(lowering_platforms=("tpu",))`` runs
# the Pallas -> Mosaic lowering stage without a device, so trace- and
# lowering-stage breakage (a renamed Mosaic param class, an index map
# with the wrong arity) fails here instead of on the first chip run.
# Whether Mosaic then COMPILES the kernel only the chip can say
# (chip_smoke.py's kernel pre-flight).

_TPU_SELF_ATTENTION_SHAPES = [
    (2, 4096, 10, 64),   # SDXL 1024 px, 64x64 level
    (2, 1024, 20, 64),   # SDXL 1024 px, 32x32 level
    (2, 4096, 8, 40),    # SD1.5 512 px, head dim 40 (lane-padded)
    (2, 9216, 5, 64),    # SD2.1 768 px 96x96 level; SVD 576x1024 72x128
    (2, 2304, 10, 64),   # SD2.1 768 px 48x48 level; SVD 576x1024 36x64
    (2, 1024, 8, 160),   # SD1.5 512 px, 32x32 level, head dim 160
]


@pytest.mark.parametrize("shape", _TPU_SELF_ATTENTION_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_flash_cross_lowers_for_tpu(shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lowered = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    ).trace(x, x, x).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_ring_flash_fused_cross_lowers_for_tpu():
    """The fused ring kernel (remote DMA + semaphores) on a 4-device
    virtual seq mesh: the scalar-prefetch grid spec, the index maps and
    the Mosaic params must at least trace and lower."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from chiaswarm_tpu.core.compat import shard_map_unchecked
    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
    from chiaswarm_tpu.ops.ring_flash_attention import ring_flash_attention

    mesh = build_mesh(MeshSpec({"seq": 4}), devices=jax.devices()[:4])
    spec = P(None, "seq", None, None)
    fn = shard_map_unchecked(
        partial(ring_flash_attention, axis_name="seq", interpret=False,
                mesh_axis_names=tuple(mesh.axis_names)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    x = jax.ShapeDtypeStruct((2, 4096, 10, 64), jnp.bfloat16)
    lowered = jax.jit(fn).trace(x, x, x).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_flash_under_a_dp_tp_mesh_is_shard_mapped(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: a flash call traced inside
    a program whose operands are sharded over a dp x tp mesh fails TPU
    lowering ("Mosaic kernels cannot be automatically partitioned" — what
    every SDXL job hit on the first four-chip run). Under the trace-time
    mesh context the pipelines enter (parallel/context.py), ops.attention
    shard_maps the call — batch on ``data``, heads on ``model`` — and the
    same program lowers."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
    from chiaswarm_tpu.parallel import param_mesh

    mesh = build_mesh(MeshSpec({"data": 2, "model": 2}),
                      devices=jax.devices()[:4])
    x = jax.ShapeDtypeStruct(
        (4, 1024, 20, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, "model", None)))
    # trace what a TPU process would: auto picks flash, non-interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def lower():  # a fresh jit each time: the dispatch is a TRACE-time pick
        return jax.jit(lambda q, k, v: attention(q, k, v)).trace(
            x, x, x).lower(lowering_platforms=("tpu",))

    with pytest.raises(NotImplementedError, match="shard_map"):
        lower()
    with param_mesh(mesh):
        assert "tpu_custom_call" in lower().as_text()


# ---- the causal kernel is a second entry: the diffusion calls are as they
# were (ISSUE 30) ---------------------------------------------------------
# sha256 of ``str(jax.make_jaxpr(...))`` taken on the commit BEFORE the
# causal kernel came (97c0079), under this suite's conftest (its matmul
# precision is in the text), at the two benchmark cells' self-attention
# shapes: the Pallas kernel's own jaxpr, its grid and its block mappings
# are in that text, so a change to ``online_softmax_block_update`` or to
# ``_pick_block`` that reaches the diffusion call moves the digest.

_DIFFUSION_SHAPES = {"sd15-512": (1, 4096, 8, 40),
                     "sdxl-1024": (1, 4096, 10, 64)}

_PARENT_JAXPR_SHA256 = {
    ("sd15-512", "flash_attention"):
        "2c11e34cc5a7634da316ba983d6ec7ae8c7febbde7f0511245414e5db48f6145",
    ("sd15-512", "attention"):
        "ba05137c3bc6f5271cd0ccb294e9b905eaf18e778205aa9f433ece46d6c46c73",
    ("sd15-512", "attention-as-on-the-chip"):
        "c4abcd9777bbbb560f27d2c3208eb4458c0c70104512f892abdb071c08cfbb50",
    ("sdxl-1024", "flash_attention"):
        "9f0e60eaddac2025392493b05784393b63599ce56fae9c26bfbf811cff1b613d",
    ("sdxl-1024", "attention"):
        "ea40404ee3fd39feccf7956809f40ebbffb9d33c38afa61b2edb56bb7958f320",
    ("sdxl-1024", "attention-as-on-the-chip"):
        "16a853df3429ee1dab1893964b9f56be521b7ef4aeaa2e230b0bcb162ad1f2f9",
}


@pytest.mark.parametrize("cell, call", list(_PARENT_JAXPR_SHA256),
                         ids=lambda v: v)
def test_diffusion_attention_traces_to_the_parents_jaxpr(cell, call,
                                                         monkeypatch):
    """``flash_attention()`` in interpret mode, ``attention()`` as the
    CPU picks (the einsum) and as a TPU process picks (the Mosaic
    kernel): textually the parent's program, and none of them reaches
    the causal entry."""
    import hashlib

    from chiaswarm_tpu.ops import causal_flash_attention as causal_module

    def refuse(*args, **kwargs):
        raise AssertionError("a diffusion call reached the causal kernel")

    monkeypatch.setattr(causal_module, "causal_flash_attention", refuse)
    monkeypatch.setattr(causal_module, "_causal_kernel", refuse)
    if call == "attention-as-on-the-chip":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if call == "flash_attention":
        def fn(q, k, v):
            return flash_attention(q, k, v, interpret=True)
    else:
        def fn(q, k, v):
            return attention(q, k, v)
    x = jax.ShapeDtypeStruct(_DIFFUSION_SHAPES[cell], jnp.bfloat16)
    text = str(jax.make_jaxpr(fn)(x, x, x))
    assert ("pallas_call" in text) == (call != "attention")
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_JAXPR_SHA256[cell, call]


# ---- the prefill's schedule (ISSUE 36): the decode entries are as they
# were, the prefill is held to the dense masked einsum ---------------------
# sha256 of ``str(jax.make_jaxpr(...))`` of the two decode entries at the
# three text cells' shapes, taken on the commit BEFORE the prefill's
# schedule changed (b828833), under this suite's conftest: the kernel's
# jaxpr, grid and block mappings are in that text. Traced as a TPU
# process traces them (the Mosaic call, not the interpreter's).

_PARENT_DECODE_JAXPR_SHA256 = {
    "deepseek-16-rows-of-128-heads":
        "33f3cf12e726c7eea410160f7873ab6518330bae18c02d66564d15cd69ec6d82",
    "ling-32-rows-of-32-heads":
        "650125356758d09f7effcbb2dbe79e0de8232eb69a7f127ba5449842099e53f0",
    "laguna-32-rows-of-48-heads-over-8":
        "5b2adb44fb94cd27511a6a6bb69fef83ddc6d4b3b959d31426997f5a84e174fe",
}


def _bf16_spec(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


@pytest.mark.parametrize("cell", list(_PARENT_DECODE_JAXPR_SHA256))
def test_the_decodes_sweeps_trace_to_the_parents_jaxpr(cell):
    """``shared_latent_attention`` (every row's heads against the 16,384
    shared latents) and ``shared_prompt_attention`` (a key-value head's
    rows against its 16,384 keys and values): the kernel body, the index
    maps and the one ``pallas_call`` they share with the prefill are,
    for them, the parent's program letter for letter."""
    import hashlib
    from unittest import mock

    from chiaswarm_tpu.ops.attention import (
        shared_latent_attention,
        shared_prompt_attention,
    )

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    if cell.startswith("laguna"):
        def fn(q, k, v, n):
            return shared_prompt_attention(q, k, v, n, scale=128 ** -0.5)

        shapes = (_bf16_spec(32, 48, 128), _bf16_spec(16384, 8, 128),
                  _bf16_spec(16384, 8, 128), scalar)
    else:
        def fn(q, cache, n):
            return shared_latent_attention(q, cache, n, value_width=512,
                                           scale=192 ** -0.5)

        rows = 16 * 128 if cell.startswith("deepseek") else 32 * 32
        shapes = (_bf16_spec(rows, 576), _bf16_spec(16384, 576), scalar)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(jax.make_jaxpr(fn)(*shapes))
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_DECODE_JAXPR_SHA256[cell]


def _dense_prefill(q, k, v, q_rotary, k_rotary, q_offset, scale):
    """softmax over every slot in float64, masked to ``s <= q_offset +
    l``; NaN in a masked slot's value counts as 0."""
    q, k, v, q_rotary, k_rotary = (
        np.asarray(x, np.float64) for x in (q, k, v, q_rotary, k_rotary))
    logits = (np.einsum("blhd,bshd->bhls", q, np.nan_to_num(k))
              + np.einsum("blhr,bsr->bhls", q_rotary,
                          np.nan_to_num(k_rotary))) * scale
    visible = np.arange(k.shape[1])[None, :] \
        <= q_offset + np.arange(q.shape[1])[:, None]
    logits = np.where(visible, logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhls,bshd->blhd", p, np.nan_to_num(v))


#: (queries, capacity, q_offset, block_q, block_kv); blocks None = the
#: entry's own pick through ``ops.attention``. The cell's blocks are 2048
#: x 1024 with the diagonal cut in 512-row tiles: 64 x 32 and 16 here.
_PREFILL_SCHEDULE_CASES = {
    "first-chunk": (64, 256, 0, 64, 32),
    "one-block": (32, 32, 0, None, None),
    "second-chunk": (64, 256, 64, 64, 32),
    "an-odd-offset": (64, 256, 37, 64, 32),
    "off-the-key-blocks-by-a-tile": (64, 256, 80, 64, 32),
    "last-chunk": (64, 256, 192, 64, 32),
    "last-chunk-own-pick": (64, 256, 192, None, None),
    "square-blocks": (64, 256, 128, 32, 32),
    "wide-key-blocks": (64, 256, 128, 32, 64),
    "a-ragged-chunk": (40, 256, 64, 32, 32),
}


@pytest.mark.parametrize("case", list(_PREFILL_SCHEDULE_CASES))
def test_the_prefills_schedule_is_the_dense_masked_einsum(case):
    """The latent-attention prefill at the DeepSeek cell's widths (128 /
    64 shared / 128, three heads), float32 operands: the sweep that ends
    at the written cache, cuts the diagonal's block pairs into tiles and
    hands the MXU one 256-deep contraction is the masked softmax over
    every slot; what lies past the written cache's last block is NaN, in
    keys, values and the shared key part, and is not read."""
    from chiaswarm_tpu.ops.causal_flash_attention import (
        causal_flash_attention,
    )

    l, s, q_offset, block_q, block_kv = _PREFILL_SCHEDULE_CASES[case]
    rng = np.random.RandomState(36)
    q, q_rotary = rng.randn(1, l, 3, 128), rng.randn(1, l, 3, 64)
    k, v, k_rotary = (rng.randn(1, s, 3, 128), rng.randn(1, s, 3, 128),
                      rng.randn(1, s, 64))
    scale = 192 ** -0.5
    want = _dense_prefill(q, k, v, q_rotary, k_rotary, q_offset, scale)
    kv = block_kv or min(l, s)
    end = -(-(q_offset + l) // kv) * kv
    for x in (k, v, k_rotary):
        x[:, end:] = np.nan
    f32 = [jnp.asarray(x, jnp.float32) for x in (q, k, v, q_rotary, k_rotary)]
    if block_q is None:
        got = attention(f32[0], f32[1], f32[2], scale=scale, causal=True,
                        q_offset=jnp.int32(q_offset),
                        shared_key=(f32[3], f32[4]))
    else:
        got = causal_flash_attention(
            f32[0], f32[1], f32[2], jnp.int32(q_offset), (f32[3], f32[4]),
            scale=scale, block_q=block_q, block_kv=block_kv, interpret=True)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5


def test_the_prefills_schedule_in_bfloat16_is_as_close_as_the_parents():
    """bfloat16 operands at the cell's widths, the last chunk: the
    largest error against the float64 masked softmax is what rounding
    the operands and the probabilities to bfloat16 gives (the parent's
    kernel, whose MXU pass rounded the float32 probabilities itself,
    read 0.0073 at the chunk's first rows on the chip and 0.0002 at its
    last: CHANGES.md, PR 36), and far from what a wrong mask gives."""
    from chiaswarm_tpu.ops.causal_flash_attention import (
        causal_flash_attention,
    )

    rng = np.random.RandomState(37)
    l, s, q_offset = 64, 256, 192
    bf16 = [jnp.asarray(x, jnp.bfloat16) for x in (
        rng.randn(1, l, 3, 128), rng.randn(1, s, 3, 128),
        rng.randn(1, s, 3, 128), rng.randn(1, l, 3, 64),
        rng.randn(1, s, 64))]
    want = _dense_prefill(*[np.asarray(x, np.float32) for x in bf16],
                          q_offset, 192 ** -0.5)
    got = np.asarray(causal_flash_attention(
        bf16[0], bf16[1], bf16[2], jnp.int32(q_offset), (bf16[3], bf16[4]),
        scale=192 ** -0.5, block_q=64, block_kv=32, interpret=True),
        np.float32)
    assert np.abs(got - want).max() < 0.01
    off_by_one = _dense_prefill(*[np.asarray(x, np.float32) for x in bf16],
                                q_offset - 1, 192 ** -0.5)
    assert np.abs(off_by_one - want).max() > 0.05


@pytest.mark.parametrize("rows", [16 * 128, 32 * 32],
                         ids=["deepseek-16-rows-of-128-heads",
                              "ling-32-rows-of-32-heads"])
def test_the_decodes_sweep_cross_lowers_for_tpu_at_the_text_cells_shapes(
        rows):
    """Every row's heads as query rows, 512 + 64 wide, against the
    16,384 shared latents with a traced prompt length, through
    ``ops.attention``: one Mosaic call named apart from the prefill's,
    handed the cache as it lies, twice (no (16384, 512) copy of the
    latents, no (16384, 128) padded copy of the rotary columns)."""
    from unittest import mock

    from chiaswarm_tpu.ops.attention import shared_latent_attention

    def fn(q, cache, prompt_len):
        return shared_latent_attention(q, cache, prompt_len,
                                       value_width=512, scale=192 ** -0.5)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jax.jit(fn).trace(
            _bf16_spec(rows, 576), _bf16_spec(16384, 576),
            jax.ShapeDtypeStruct((), jnp.int32),
        ).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "shared_latent_attention" in text
    assert "x16384x576xbf16" in text
    assert "16384x512x" not in text and "16384x128x" not in text


@pytest.mark.parametrize("heads", [32, 128],
                         ids=["ling-32-heads", "deepseek-128-heads"])
def test_causal_flash_cross_lowers_for_tpu_at_the_text_cells_shapes(heads):
    """One 2048-token chunk (128-wide keys and values, the 64-wide rotary
    key shared) against 16,384 slots, through ``attention(causal=True)``
    with a traced offset: the scalar-prefetch grid spec, its clamped
    index maps and the key axis whose bound is traced with the offset
    (2048-row query blocks against 1024-key blocks, as far as the cache
    is written) trace and lower for Mosaic, as one program for every
    chunk."""
    def fn(q, k, v, q_offset, q_rotary, k_rotary):
        return attention(q, k, v, scale=192 ** -0.5, causal=True,
                         q_offset=q_offset, shared_key=(q_rotary, k_rotary))

    from unittest import mock

    spec = _bf16_spec
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        traced = jax.jit(fn).trace(
            spec(1, 2048, heads, 128), spec(1, 16384, heads, 128),
            spec(1, 16384, heads, 128), jax.ShapeDtypeStruct((), jnp.int32),
            spec(1, 2048, heads, 64), spec(1, 16384, 64))
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "causal_flash_attention" in text
    program = str(traced.jaxpr)
    assert f"grid=(1, {heads}, 1, DynamicGridDim)" in program
    assert "Blocked(block_size=2048)" in program
    assert "Blocked(block_size=1024)" in program


# ---- the grouped-query entries of the causal kernel's module (ISSUE 35) ----


@pytest.mark.parametrize("entry", ["causal_flash_attention",
                                   "window_flash_attention",
                                   "shared_prompt_attention"])
def test_the_grouped_entries_cross_lower_for_tpu_at_the_laguna_cells_shapes(
        entry):
    """A full layer's chunk (48 heads over 8 key-value heads against
    16,384 slots), a sliding layer's (64 heads against its 512 + 2,048
    local buffer, window 512) and a full layer's decode (32 rows x 48
    heads behind a traced prompt length), through ``ops.attention``: one
    Mosaic call each, under its own operation name, with the keys and
    values handed over as they lie (no copy repeated to the query
    heads)."""
    from unittest import mock

    from chiaswarm_tpu.ops.attention import shared_prompt_attention

    spec, scalar = _bf16_spec, jax.ShapeDtypeStruct((), jnp.int32)
    if entry == "shared_prompt_attention":
        def fn(q, k, v, n):
            return shared_prompt_attention(q, k, v, n, scale=128 ** -0.5)

        shapes = (spec(32, 48, 128), spec(16384, 8, 128),
                  spec(16384, 8, 128), scalar)
    else:
        windowed = entry == "window_flash_attention"

        def fn(q, k, v, q_offset):
            return attention(q, k, v, scale=128 ** -0.5, causal=True,
                             q_offset=q_offset,
                             window=512 if windowed else None)

        slots, heads = (2560, 64) if windowed else (16384, 48)
        shapes = (spec(1, 2048, heads, 128), spec(1, slots, 8, 128),
                  spec(1, slots, 8, 128), scalar)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert entry in text
    keys = shapes[1].shape[-3]
    assert f"x{keys}x1024xbf16" in text          # 8 heads of 128, as stored
    assert f"x{keys}x6144x" not in text and f"x{keys}x8192x" not in text
