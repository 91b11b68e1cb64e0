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


# ---- the decode's sweep is a second entry of the causal kernel's module:
# the prefill's call is as it was (ISSUE 34) --------------------------------
# sha256 of ``str(jax.make_jaxpr(...))`` of ``attention(causal=True)`` at
# the two text cells' prefill shapes, taken on the commit BEFORE the
# kernel body learned of rows that share a position (e2e2d2b), under this
# suite's conftest: the kernel's jaxpr, grid and block mappings are in
# that text. Traced as a TPU process traces it (the Mosaic call, not
# the interpreter's), like the cross-lowering below, whose cached trace
# it shares.

_PARENT_PREFILL_JAXPR_SHA256 = {
    32: "f53c4801f53280967ce368584d6ee3423b68e50b5c703712b3f302b244b764ad",
    128: "68d95684f3253799aef34836c928e3bc30f8ef23b78673de610e6c0391726516",
}


def _bf16_spec(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


@pytest.mark.parametrize("heads", list(_PARENT_PREFILL_JAXPR_SHA256),
                         ids=["ling-32-heads", "deepseek-128-heads"])
def test_the_prefills_causal_call_traces_to_the_parents_jaxpr(heads):
    """One 2048-token chunk against 16,384 slots with the shared rotary
    key: at one row a position the generalised kernel body is the
    parent's program letter for letter (so bit for bit in its results)."""
    import hashlib
    from unittest import mock

    def fn(q, k, v, q_offset, q_rotary, k_rotary):
        return attention(q, k, v, scale=192 ** -0.5, causal=True,
                         q_offset=q_offset, shared_key=(q_rotary, k_rotary))

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(jax.make_jaxpr(fn)(
            _bf16_spec(1, 2048, heads, 128),
            _bf16_spec(1, 16384, heads, 128),
            _bf16_spec(1, 16384, heads, 128),
            jax.ShapeDtypeStruct((), jnp.int32),
            _bf16_spec(1, 2048, heads, 64), _bf16_spec(1, 16384, 64)))
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PARENT_PREFILL_JAXPR_SHA256[heads]


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


def test_causal_flash_cross_lowers_for_tpu_at_the_text_cells_shapes():
    """One 2048-token chunk of 32 heads (128-wide keys and values, the
    64-wide rotary key shared) against 16,384 slots, through
    ``attention(causal=True)`` with a traced offset: the scalar-prefetch
    grid spec and its clamped index maps trace and lower for Mosaic."""
    def fn(q, k, v, q_offset, q_rotary, k_rotary):
        return attention(q, k, v, scale=192 ** -0.5, causal=True,
                         q_offset=q_offset, shared_key=(q_rotary, k_rotary))

    from unittest import mock

    spec = _bf16_spec
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jax.jit(fn).trace(
            spec(1, 2048, 32, 128), spec(1, 16384, 32, 128),
            spec(1, 16384, 32, 128), jax.ShapeDtypeStruct((), jnp.int32),
            spec(1, 2048, 32, 64), spec(1, 16384, 64),
        ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


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
