"""swarmkernel (ISSUE 18): the fused ring-flash kernel, hermetically.

On the virtual 8-device CPU mesh (tests/conftest.py) the Pallas kernel
runs in interpret mode, so these tests validate the in-kernel blockwise
recurrence itself — the same `_hop_kernel` the TPU path drives — against
BOTH oracles named by the acceptance criteria:

- the ppermute ring scan (parallel/ring_attention.py), the exactness
  oracle for the hop-by-hop combine; and
- the unsharded dense/flash path, the golden single-chip answer.

Tolerances are the repo's torch-parity bar (rtol/atol 2e-4,
tests/test_parallel.py). The activation-quantization seam
(CHIASWARM_ACTIVATIONS, convert/quantize.py) rides along: default-off
identity, per-tensor absmax bounds, cache-key folding, and the < 5%%
end-to-end forward-parity gate per diffusion family kind.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chiaswarm_tpu.core.compat import shard_map, shard_map_unchecked
from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
from chiaswarm_tpu.ops.attention import _xla_attention
from chiaswarm_tpu.ops.ring_flash_attention import ring_flash_attention
from chiaswarm_tpu.parallel.ring_attention import ring_attention

RTOL = ATOL = 2e-4


def _qkv(seed: int, b: int, l: int, h: int, d: int):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, l, h, d), jnp.float32),
            jax.random.normal(kk, (b, l, h, d), jnp.float32),
            jax.random.normal(kv, (b, l, h, d), jnp.float32))


def _ring_flash_fn(mesh, spec, **kw):
    from functools import partial

    return shard_map_unchecked(
        partial(ring_flash_attention, axis_name="seq",
                mesh_axis_names=tuple(mesh.axis_names), **kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)


@pytest.mark.parametrize("sp", [4, 8])
def test_ring_flash_matches_ring_and_dense(sp):
    """The acceptance line: interpret-mode ring-flash == ppermute ring
    == dense attention on seq=4 AND seq=8 meshes, torch-parity bar."""
    mesh = build_mesh(MeshSpec({"seq": sp}), devices=jax.devices()[:sp])
    b, l, h, d = 2, 128, 2, 32
    q, k, v = _qkv(sp, b, l, h, d)
    spec = P(None, "seq", None, None)

    fused = jax.jit(_ring_flash_fn(mesh, spec))(q, k, v)
    ppermute = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))(q, k, v)
    dense = _xla_attention(q, k, v, d ** -0.5)

    np.testing.assert_allclose(np.asarray(fused), np.asarray(ppermute),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                               rtol=RTOL, atol=ATOL)


def test_ring_flash_matches_unsharded_flash():
    """Against the OTHER oracle the issue names: the single-chip Pallas
    flash kernel in interpret mode — same blockwise recurrence, no
    ring; proves the hop combine is exactly the flash accumulator."""
    from chiaswarm_tpu.ops.flash_attention import flash_attention

    mesh = build_mesh(MeshSpec({"seq": 4}), devices=jax.devices()[:4])
    b, l, h, d = 2, 128, 2, 32
    q, k, v = _qkv(3, b, l, h, d)
    spec = P(None, "seq", None, None)
    fused = jax.jit(_ring_flash_fn(mesh, spec))(q, k, v)
    flash = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(flash),
                               rtol=RTOL, atol=ATOL)


def test_ring_flash_mixed_data_seq_mesh():
    """The divergence family's trigger shape (R11 / r06): a two-axis
    data=2 x seq=4 shard_map — batch sharded on data, tokens ringed."""
    mesh = build_mesh(MeshSpec({"data": 2, "seq": 4}))
    b, l, h, d = 2, 128, 2, 32
    q, k, v = _qkv(4, b, l, h, d)
    spec = P("data", "seq", None, None)
    fused = jax.jit(_ring_flash_fn(mesh, spec))(q, k, v)
    dense = _xla_attention(q, k, v, d ** -0.5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                               rtol=RTOL, atol=ATOL)


def test_ring_flash_inner_blocking():
    """Inner-blocked hop (block_q=block_kv=16 over a 32-token shard)
    must match the whole-shard default — the blocked path is what the
    TPU grid actually runs at SDXL sizes."""
    mesh = build_mesh(MeshSpec({"seq": 4}), devices=jax.devices()[:4])
    b, l, h, d = 2, 128, 2, 32
    q, k, v = _qkv(5, b, l, h, d)
    spec = P(None, "seq", None, None)
    blocked = jax.jit(_ring_flash_fn(mesh, spec, block_q=16,
                                     block_kv=16))(q, k, v)
    dense = _xla_attention(q, k, v, d ** -0.5)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                               rtol=RTOL, atol=ATOL)


def test_dispatch_impl_ring_flash(monkeypatch):
    """ops.attention dispatch: impl='ring_flash' under param_mesh
    routes the fused kernel and matches dense; without a mesh the
    explicit impl= contract still raises."""
    from chiaswarm_tpu.ops.attention import attention
    from chiaswarm_tpu.parallel import param_mesh

    monkeypatch.setenv("CHIASWARM_RING_MIN_TOKENS", "1")
    mesh = build_mesh(MeshSpec({"seq": 4}), devices=jax.devices()[:4])
    b, l, h, d = 2, 64, 2, 16
    q, k, v = _qkv(6, b, l, h, d)
    ref = _xla_attention(q, k, v, d ** -0.5)
    with param_mesh(mesh):
        got = attention(q, k, v, impl="ring_flash")
        # cross-attention (tiny KV) stays local even for ring kinds
        cross = attention(q, k[:, :7], v[:, :7], impl="ring_flash")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    assert cross.shape == q.shape
    with pytest.raises(ValueError, match="sequence-parallel mesh"):
        attention(q, k, v, impl="ring_flash")


def test_env_override_is_advisory(monkeypatch):
    """CHIASWARM_ATTENTION=ring_flash: on a seq mesh the auto pick is
    overridden to the fused kernel; OFF the mesh it must NOT crash (a
    fleet-wide env roll reaches workers with no seq axis) — those fall
    back to the local paths."""
    from chiaswarm_tpu.ops.attention import attention
    from chiaswarm_tpu.parallel import param_mesh

    monkeypatch.setenv("CHIASWARM_RING_MIN_TOKENS", "1")
    monkeypatch.setenv("CHIASWARM_ATTENTION", "ring_flash")
    mesh = build_mesh(MeshSpec({"seq": 4}), devices=jax.devices()[:4])
    b, l, h, d = 2, 64, 2, 16
    q, k, v = _qkv(7, b, l, h, d)
    ref = _xla_attention(q, k, v, d ** -0.5)
    with param_mesh(mesh):
        got = attention(q, k, v)  # auto, env-overridden
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    # advisory off-mesh: falls back instead of raising
    local = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(local), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_ring_flash_taps_feed_bisect(monkeypatch):
    """The scan path's per-hop probes (ring_flash.hop_rowmax/rowsum/
    hop_acc + ring_flash.out) record under the same 'ring' numerics
    token as the ppermute ring — the stream divergence_bisect's
    seq_parallel_ring_flash config aligns against its fp twin."""
    from chiaswarm_tpu.obs import numerics

    monkeypatch.setenv("CHIASWARM_NUMERICS", "ring")
    mesh = build_mesh(MeshSpec({"seq": 4}), devices=jax.devices()[:4])
    b, l, h, d = 2, 64, 2, 16
    q, k, v = _qkv(8, b, l, h, d)
    spec = P(None, "seq", None, None)
    out = jax.jit(_ring_flash_fn(mesh, spec))(q, k, v)
    jax.block_until_ready(out)
    numerics.flush()
    records = numerics.RING.snapshot()
    probes = {r["probe"] for r in records}
    assert "ring_flash.out" in probes
    assert "ring_flash.hop_rowmax" in probes
    # per-hop x per-shard identity, the bisect's alignment key
    hops = [r for r in records if r["probe"] == "ring_flash.hop_rowsum"]
    assert {(r["step"], r["shard"]) for r in hops} >= {
        (hop, shard) for hop in range(4) for shard in range(4)}


# ---------------------------------------------------------------------------
# low-precision activations (CHIASWARM_ACTIVATIONS)


def test_activations_default_off_identity(monkeypatch):
    monkeypatch.delenv("CHIASWARM_ACTIVATIONS", raising=False)
    from chiaswarm_tpu.convert.quantize import (
        activations_enabled,
        fake_quant_activation,
    )

    assert not activations_enabled()
    x = jnp.arange(8.0).reshape(2, 4)
    assert fake_quant_activation(x, tag="t") is x


def test_activations_int8_absmax_bounds(monkeypatch):
    """Per-tensor dynamic absmax: every element lands within half a
    code of its fp value, and the absmax element round-trips exactly."""
    monkeypatch.setenv("CHIASWARM_ACTIVATIONS", "int8")
    from chiaswarm_tpu.convert.quantize import fake_quant_activation

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64), jnp.float32) * 3
    q = np.asarray(fake_quant_activation(x, tag="t"))
    scale = float(np.max(np.abs(np.asarray(x)))) / 127.0
    assert np.all(np.abs(np.asarray(x) - q) <= scale / 2 + 1e-8)
    i = np.unravel_index(np.argmax(np.abs(np.asarray(x))), x.shape)
    np.testing.assert_allclose(q[i], np.asarray(x)[i], rtol=1e-6)
    # integers are non-float: identity, never quantized
    ints = jnp.arange(5)
    assert fake_quant_activation(ints, tag="t") is ints


def test_activations_fp8_parity(monkeypatch):
    """fp8 (e4m3 via core/compat probe; degrades to int8 where the
    dtype/hardware is absent) keeps a unit-scale tensor within a few
    percent — the coarse-grid bound, not bit exactness."""
    monkeypatch.setenv("CHIASWARM_ACTIVATIONS", "fp8")
    from chiaswarm_tpu.convert.quantize import (
        activations_format,
        fake_quant_activation,
    )

    assert activations_format() in ("fp8", "int8")
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), jnp.float32)
    q = np.asarray(fake_quant_activation(x, tag="t"))
    rel = (np.linalg.norm(np.asarray(x) - q)
           / np.linalg.norm(np.asarray(x)))
    assert rel < 0.05, f"fp8 fake-quant rel err {rel:.4f}"


def test_activations_unknown_value_off(monkeypatch):
    monkeypatch.setenv("CHIASWARM_ACTIVATIONS", "int4")
    from chiaswarm_tpu.convert.quantize import activations_format

    assert activations_format() == "off"


def test_activation_cache_key_folds(monkeypatch):
    """The compile-cache discipline: the activations format folds into
    static_cache_key ONLY when enabled — default-off keys stay
    byte-identical to pre-ISSUE-18 keys (no fleet-wide recompile)."""
    from chiaswarm_tpu.core.compile_cache import static_cache_key

    monkeypatch.delenv("CHIASWARM_ACTIVATIONS", raising=False)
    monkeypatch.delenv("CHIASWARM_NUMERICS", raising=False)
    static = {"size": 64, "steps": 2}
    base = static_cache_key(1, "unet", static)
    assert not any("activations" in str(part) for part in base)
    monkeypatch.setenv("CHIASWARM_ACTIVATIONS", "int8")
    keyed = static_cache_key(1, "unet", static)
    assert keyed != base
    assert ("activations", "int8") in keyed
    # restore-off restores the historical key byte-identically
    monkeypatch.delenv("CHIASWARM_ACTIVATIONS", raising=False)
    assert static_cache_key(1, "unet", static) == base


def test_attention_int8_activations_parity(monkeypatch):
    """attention() with the quantized q/k/v seam engaged stays within
    the coarse bound vs the fp path on normal-scale inputs."""
    from chiaswarm_tpu.ops.attention import attention

    b, l, h, d = 2, 64, 2, 16
    q, k, v = _qkv(9, b, l, h, d)
    ref = np.asarray(attention(q, k, v, impl="xla"))
    monkeypatch.setenv("CHIASWARM_ACTIVATIONS", "int8")
    got = np.asarray(attention(q, k, v, impl="xla"))
    rel = np.linalg.norm(ref - got) / np.linalg.norm(ref)
    assert rel < 0.05, f"int8 activation attention rel err {rel:.4f}"


@pytest.mark.parametrize("family", [
    "tiny",
    pytest.param("tiny_xl", marks=pytest.mark.slow),
])
def test_int8_activation_forward_parity_per_family_kind(family,
                                                        monkeypatch):
    """The ISSUE-18 acceptance gate, mirroring the PR-8 weights gate
    (tests/test_residency.py): generated images through the REAL
    registry with CHIASWARM_ACTIVATIONS=int8 must stay within 5%%
    relative error of the fp path, per diffusion family kind."""
    monkeypatch.setenv("CHIASWARM_STEPPER", "0")
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.pipelines.diffusion import GenerateRequest

    def registry():
        return ModelRegistry(
            catalog=[{"name": family, "family": family}],
            allow_random=True)

    req = GenerateRequest(prompt="parity", steps=2, guidance_scale=7.5,
                          height=64, width=64, batch=1, seed=11)
    monkeypatch.delenv("CHIASWARM_ACTIVATIONS", raising=False)
    img_fp, _ = registry().pipeline(family)(req)

    monkeypatch.setenv("CHIASWARM_ACTIVATIONS", "int8")
    img_q, _ = registry().pipeline(family)(req)

    assert img_q.shape == img_fp.shape
    diff = np.abs(img_fp.astype(np.float32) - img_q.astype(np.float32))
    rel = (np.linalg.norm(diff)
           / max(np.linalg.norm(img_fp.astype(np.float32)), 1e-9))
    assert diff.mean() < 4.0, f"mean abs uint8 diff {diff.mean():.2f}"
    assert rel < 0.05, f"relative error {rel:.4f}"
