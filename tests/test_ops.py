"""Kernel tests: Pallas flash attention (interpret mode on CPU) vs the
einsum reference — the golden-value strategy SURVEY.md §4 calls for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chiaswarm_tpu.ops.attention import _xla_attention, attention
from chiaswarm_tpu.ops.flash_attention import flash_attention


#: (b, l, s, h, d) in 64 x 64 blocks: key lengths that fill whole key
#: blocks (no mask is traced) and lengths that leave the last one padded
#: (masked there alone), at the UNets' head sizes 40 / 64 / 80 / 160 and
#: the lane width
_FLASH_CASES = [
    (2, 64, 64, 4, 40),    # SD1.5-style self-attention head_dim 40
    (1, 100, 77, 2, 64),   # cross-attention: text KV of 77 tokens
    (1, 300, 300, 2, 80),  # non-multiple-of-block lengths
    (2, 128, 128, 1, 128), # exact lane-width head dim
    (1, 96, 256, 2, 64),   # four whole key blocks
    (2, 130, 192, 2, 40),  # three whole key blocks, a padded query block
    (1, 128, 200, 2, 160), # a padded last key block, head dim 160
    (1, 64, 128, 3, 80),   # two whole key blocks, head dim 80
    (1, 64, 136, 2, 160),  # a last key block of 8 keys, head dim 160
]


def _flash_case(case, dtype: str):
    """The float32 cases keep the ids they had before bfloat16 joined."""
    name = "-".join(map(str, case))
    return pytest.param(*case, dtype, id=name if dtype == "float32"
                        else f"{name}-{dtype}")


@pytest.mark.parametrize(
    "b,l,s,h,d,dtype",
    [_flash_case(case, dtype) for dtype in ("float32", "bfloat16")
     for case in _FLASH_CASES],
)
def test_flash_matches_einsum(b, l, s, h, d, dtype):
    """The kernel against the einsum at the tolerances of each I/O dtype:
    float32 2e-4; bfloat16 3e-2 against the float32 einsum of the same
    bfloat16 inputs (the probabilities reach the MXU as bfloat16)."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (b, n, h, d), jnp.dtype(dtype))
               for key, n in ((kq, l), (kk, s), (kv, s)))
    scale = d ** -0.5
    ref = _xla_attention(*(x.astype(jnp.float32) for x in (q, k, v)), scale)
    got = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    assert got.dtype == q.dtype
    tol = 2e-4 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


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


@pytest.mark.parametrize("shape", _TPU_SELF_ATTENTION_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_no_key_block_is_masked_at_the_served_shapes(shape):
    """Every served self-attention length fills whole key blocks of the
    picked size: ``block_steps`` counts no masked step, every step of the
    grid whole, and the kernel's jaxpr holds no mask at all (no iota, no
    comparison with the key length)."""
    from chiaswarm_tpu.ops.flash_attention import _blocks, block_steps

    b, n, h, _ = shape
    block_q, block_kv = _blocks(n, n, None, None)
    steps = block_steps(b * h, n, n)
    assert steps == {"whole": b * h * -(-n // block_q) * (n // block_kv),
                     "masked": 0}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, interpret=True))(x, x, x))
    assert "pallas_call" in text and "iota" not in text


#: (batch x heads, queries, keys, block_q, block_kv) whose keys leave the
#: last key block padded, and two that do not
_BLOCK_STEP_CASES = [(3, 300, 77, 64, 64), (2, 100, 300, 64, 64),
                     (4, 64, 136, 32, 64), (1, 2304, 2304, 2048, 1024),
                     (5, 9216, 9216, None, None),
                     (2, 256, 256, 64, 64)]


@pytest.mark.parametrize("bh, l, s, block_q, block_kv", _BLOCK_STEP_CASES)
def test_block_steps_counts_the_kernels_grid(bh, l, s, block_q, block_kv):
    """``block_steps`` against the grid walked step by step: a step is
    masked when its key block reaches past ``s``, which is the last key
    block of each (batch x head, query block) and only where ``s`` is no
    multiple of the key block."""
    from chiaswarm_tpu.ops.flash_attention import _blocks, block_steps

    bq, bkv = _blocks(l, s, block_q, block_kv)
    want = {"whole": 0, "masked": 0}
    for _ in range(bh):
        for _ in range(-(-l // bq)):
            for j in range(-(-s // bkv)):
                want["masked" if (j + 1) * bkv > s else "whole"] += 1
    assert block_steps(bh, l, s, block_q, block_kv) == want
    assert want["masked"] == (bh * -(-l // bq) if s % bkv else 0)


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


# ---- the causal kernel is a second entry: the diffusion calls are held to
# a digest ----------------------------------------------------------------
# sha256 of ``str(jax.make_jaxpr(...))`` under this suite's conftest (its
# matmul precision is in the text), at the two benchmark cells'
# self-attention shapes: the Pallas kernel's own jaxpr, its grid and its
# block mappings are in that text, so a change to ``update_tile``, to the
# kernel's masking or to ``_pick_block`` that reaches the diffusion call
# moves the digest. The einsum's ("attention") are those of the commit
# before the causal kernel came (97c0079); the kernel's are of its update
# by ``update_tile`` with no padding mask at these shapes.

_DIFFUSION_SHAPES = {"sd15-512": (1, 4096, 8, 40),
                     "sdxl-1024": (1, 4096, 10, 64)}

_PARENT_JAXPR_SHA256 = {
    ("sd15-512", "flash_attention"):
        "868f870fb1e61e12fc75e59670d8c1a8bf5f361df0463b111c9559fb8ee057a3",
    ("sd15-512", "attention"):
        "ba05137c3bc6f5271cd0ccb294e9b905eaf18e778205aa9f433ece46d6c46c73",
    ("sd15-512", "attention-as-on-the-chip"):
        "cf5b18e0e28b55f863614cf97cca004882354737d712cd87c775ab1ed0a0a0c0",
    ("sdxl-1024", "flash_attention"):
        "8df29ac82b6d087dc67dec803fa543a26fcc80e40b1d6e1d42a09f2c453a4b53",
    ("sdxl-1024", "attention"):
        "ea40404ee3fd39feccf7956809f40ebbffb9d33c38afa61b2edb56bb7958f320",
    ("sdxl-1024", "attention-as-on-the-chip"):
        "cc0ee3c6289ea90a39a0f4d8b695ac589cd3db6870a3787c596c7fcd5487e95c",
}


@pytest.mark.parametrize("cell, call", list(_PARENT_JAXPR_SHA256),
                         ids=lambda v: v)
def test_diffusion_attention_traces_to_the_parents_jaxpr(cell, call,
                                                         monkeypatch):
    """``flash_attention()`` in interpret mode, ``attention()`` as the
    CPU picks (the einsum) and as a TPU process picks (the Mosaic
    kernel): textually the pinned program, and none of them reaches
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


# ---- the latent form of the prefill: the sweep up-projects the latents
# itself --------------------------------------------------------------------


def _latent_case(seed, heads, l, s, rank=128):
    """Queries (1, l, H, 128) and their rotary part (1, l, H, 64), the
    cache (1, s, rank + 64) and W_ukv (rank, H, 128 + 128), scaled so that
    keys, values and logits are of unit size."""
    rng = np.random.RandomState(seed)
    return (rng.randn(1, l, heads, 128), rng.randn(1, l, heads, 64),
            rng.randn(1, s, rank + 64), rng.randn(rank, heads, 256) / 16.0)


def _up_projected(cache, w_ukv, rank=128):
    """Keys and values (1, s, H, 128) of the cache's latents, float64."""
    kv = np.einsum("bsc,chd->bshd", np.asarray(cache, np.float64)[..., :rank],
                   np.asarray(w_ukv, np.float64))
    return kv[..., :128], kv[..., 128:]


def _run_latent(q, q_rotary, cache, w_ukv, q_offset, blocks, rank=128):
    from chiaswarm_tpu.ops.causal_flash_attention import (
        causal_flash_attention,
    )

    return np.asarray(causal_flash_attention(
        q, cache, w_ukv, jnp.int32(q_offset), (q_rotary, cache[..., rank:]),
        scale=192 ** -0.5, block_q=blocks[0], block_kv=blocks[1],
        interpret=True), np.float32)


@pytest.mark.parametrize("q_offset", [0, 128, 192],
                         ids=["first-chunk", "a-middle-chunk", "last-chunk"])
@pytest.mark.parametrize("heads", [128, 32],
                         ids=["deepseek-128-heads", "ling-32-heads"])
def test_the_latent_prefill_is_the_dense_softmax_of_up_projected_keys(
        heads, q_offset):
    """Latents and W_ukv in place of keys and values (a 128-wide latent
    read from the cache as it lies, the rotary key beside it), 64 queries
    against 256 slots in 64 x 32 blocks, float32: the masked softmax over
    every slot of the keys and values up-projected in float64. A wrong
    head's columns (the next head's) miss by far more."""
    q, q_rotary, cache, w_ukv = _latent_case(38, heads, 64, 256)
    k, v = _up_projected(cache, w_ukv)
    want = _dense_prefill(q, k, v, q_rotary, cache[..., 128:], q_offset,
                          192 ** -0.5)
    f32 = [jnp.asarray(x, jnp.float32) for x in (q, q_rotary, cache, w_ukv)]
    got = _run_latent(*f32, q_offset, (64, 32))
    assert got.shape == (1, 64, heads, 128)
    assert np.abs(got - want).max() < 1e-5
    shifted = jnp.roll(f32[3], 1, axis=1)
    assert np.abs(_run_latent(*f32[:3], shifted, q_offset, (64, 32))
                  - want).max() > 0.05


def test_the_latent_prefill_in_bfloat16_is_as_close_as_the_unfused_form():
    """bfloat16 operands, the last chunk: the parent's form (keys and
    values up-projected in XLA, rounded to bfloat16, then the sweep over
    them) and the latent form both stay within the parent's own bfloat16
    limit of the float64 softmax, and within it of each other: the
    kernel's up-projection rounds what XLA's did."""
    from chiaswarm_tpu.models.text_layers import proj
    from chiaswarm_tpu.ops.causal_flash_attention import (
        causal_flash_attention,
    )

    bf16 = [jnp.asarray(x, jnp.bfloat16)
            for x in _latent_case(39, 4, 64, 256)]
    q, q_rotary, cache, w_ukv = bf16
    k, v = _up_projected(*[np.asarray(x, np.float32) for x in (cache, w_ukv)])
    want = _dense_prefill(np.asarray(q, np.float32), k, v,
                          np.asarray(q_rotary, np.float32),
                          np.asarray(cache, np.float32)[..., 128:], 192,
                          192 ** -0.5)
    got = _run_latent(*bf16, 192, (64, 32))
    kv = proj(cache[..., :128], w_ukv.reshape(128, -1)).reshape(1, 256, 4,
                                                                 256)
    unfused = np.asarray(causal_flash_attention(
        q, kv[..., :128], kv[..., 128:], jnp.int32(192),
        (q_rotary, cache[..., 128:]), scale=192 ** -0.5, block_q=64,
        block_kv=32, interpret=True), np.float32)
    assert np.abs(unfused - want).max() < 0.01
    assert np.abs(got - want).max() < 0.01
    assert np.abs(got - unfused).max() < 0.01


@pytest.mark.parametrize("q_offset, blocks", [
    (0, (64, 32)), (128, (64, 32)), (192, (64, 32)), (128, (32, 64))],
    ids=["first-chunk", "a-middle-chunk", "last-chunk", "wide-key-blocks"])
def test_the_latent_prefill_reads_no_latent_past_the_written_cache(q_offset,
                                                                    blocks):
    """Every cache row from ``q_offset + L`` on is NaN, latent and rotary
    key alike: a block that was read would up-project NaN keys and
    values, and 0 x NaN would reach the accumulator, so the clean answer
    shows that those blocks are neither fetched nor up-projected."""
    q, q_rotary, cache, w_ukv = _latent_case(40, 4, 64, 256)
    k, v = _up_projected(cache, w_ukv)
    want = _dense_prefill(q, k, v, q_rotary, cache[..., 128:], q_offset,
                          192 ** -0.5)
    cache[:, q_offset + 64:] = np.nan
    f32 = [jnp.asarray(x, jnp.float32) for x in (q, q_rotary, cache, w_ukv)]
    got = _run_latent(*f32, q_offset, blocks)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5


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


@pytest.mark.parametrize("heads", [32, 128],
                         ids=["ling-32-heads", "deepseek-128-heads"])
def test_the_latent_prefill_cross_lowers_for_tpu_at_the_text_cells_shapes(
        heads):
    """One 2048-token chunk of the latent form through
    ``ops.attention.latent_prefill_attention`` with a traced offset: one
    Mosaic call under the prefill's operation name, handed the (16384,
    576) cache as it lies and W_ukv as stored, and no (16384, H x 128)
    keys or values anywhere in the program; a head's (512, 128 + 128)
    block of W_ukv and a key block's (1024, 512) latents."""
    from unittest import mock

    from chiaswarm_tpu.ops.attention import latent_prefill_attention

    def fn(q, cache, wukv, q_offset, q_rotary):
        return latent_prefill_attention(
            q, cache, wukv.reshape(512, heads, 256), q_offset,
            shared_key=(q_rotary, cache[..., 512:]), scale=192 ** -0.5)

    spec = _bf16_spec
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        traced = jax.jit(fn).trace(
            spec(1, 2048, heads, 128), spec(1, 16384, 576),
            spec(512, heads * 256), jax.ShapeDtypeStruct((), jnp.int32),
            spec(1, 2048, heads, 64))
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "causal_flash_attention" in text
    assert "x16384x576xbf16" in text and f"512x{heads * 256}xbf16" in text
    assert f"16384x{heads * 128}x" not in text
    program = str(traced.jaxpr)
    assert f"grid=(1, {heads}, 1, DynamicGridDim)" in program
    assert "block_shape=(Blocked(block_size=512), Blocked(block_size=256))" \
        in program
    assert ("block_shape=(Blocked(block_size=1), Blocked(block_size=1024), "
            "Blocked(block_size=512))") in program


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


# sha256 of ``str(jax.make_jaxpr(...))`` of the grouped prefill entries at
# the Laguna cell's shapes, taken on a04798d (before the latent form of
# the prefill came into the kernel) under this suite's conftest, traced as
# a TPU process traces them; the decode entries are held above.
_PARENT_GROUPED_JAXPR_SHA256 = {
    "causal_flash_attention-48-heads-over-8":
        "85169fd83591ae426c90516aa5f0d02015a5758a099f3ad044ea61dfc66d94d4",
    "window_flash_attention-64-heads-over-8":
        "a4b1a8cf30ee73257a768ae0be570b8530ec09dd43ddc9b3b97bb66253fcbe45",
}


@pytest.mark.parametrize("entry", list(_PARENT_GROUPED_JAXPR_SHA256))
def test_the_grouped_prefills_trace_to_the_parents_jaxpr(entry):
    """A full layer's chunk of 48 heads over 8 key-value heads against
    16,384 slots and a sliding layer's of 64 against its 2,560-slot local
    buffer: the kernel body, index maps and ``pallas_call`` they share
    with the latent form are, for them, the parent's letter for letter."""
    import hashlib
    from unittest import mock

    windowed = entry.startswith("window")
    slots, heads = (2560, 64) if windowed else (16384, 48)

    def fn(q, k, v, q_offset):
        return attention(q, k, v, scale=128 ** -0.5, causal=True,
                         q_offset=q_offset, window=512 if windowed else None)

    shapes = (_bf16_spec(1, 2048, heads, 128), _bf16_spec(1, slots, 8, 128),
              _bf16_spec(1, slots, 8, 128),
              jax.ShapeDtypeStruct((), jnp.int32))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(jax.make_jaxpr(fn)(*shapes))
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_GROUPED_JAXPR_SHA256[entry]
