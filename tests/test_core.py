import jax
import numpy as np
import pytest

from chiaswarm_tpu.core.chip_pool import ChipPool, SlotBusy
from chiaswarm_tpu.core.compile_cache import (
    LruCache,
    bucket_batch,
    bucket_image_size,
)
from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
from chiaswarm_tpu.core.rng import draw_seed, key_for_seed, per_sample_keys


def test_mesh_auto_factorization():
    mesh = build_mesh(MeshSpec({"data": -1}))
    assert mesh.devices.size == 8
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "data": 8, "model": 1, "seq": 1,
    }


def test_mesh_explicit_shape(mesh8):
    assert dict(zip(mesh8.axis_names, mesh8.devices.shape)) == {
        "data": 4, "model": 2, "seq": 1,
    }


def test_mesh_bad_shape_raises():
    with pytest.raises(ValueError):
        build_mesh(MeshSpec({"data": 3}))
    with pytest.raises(ValueError):
        build_mesh(MeshSpec({"data": -1, "model": -1}))


def test_derive_mesh_spec_policy(monkeypatch):
    """Default dp x tp policy: tp engages exactly when the heaviest
    family's params exceed the per-chip budget; everything else is dp."""
    from chiaswarm_tpu.core.mesh import (
        derive_mesh_spec,
        resident_param_budget_bytes,
    )

    gib = 1024**3
    # the operator's byte figure outranks both HBM fractions: the tp bar
    # and the ledger's budget move together under it
    monkeypatch.setenv("CHIASWARM_RESIDENCY_BUDGET", str(12 * gib))
    assert derive_mesh_spec(8, 7 * gib, hbm_bytes=16 * gib).shape == \
        {"data": 8, "model": 1}
    assert resident_param_budget_bytes(16 * gib) == 12 * gib
    monkeypatch.delenv("CHIASWARM_RESIDENCY_BUDGET")
    assert resident_param_budget_bytes(10 * gib) == 6 * gib
    # single chip: trivially dp=1
    assert derive_mesh_spec(1, 100 * gib).shape == {"data": 1}
    # small model on 8 chips: dp-only
    assert derive_mesh_spec(8, 2 * gib, hbm_bytes=16 * gib).shape == \
        {"data": 8, "model": 1}
    # SDXL-class (~7 GB bf16) exceeds 0.35 * 16 GiB -> tp=2
    assert derive_mesh_spec(8, 7 * gib, hbm_bytes=16 * gib).shape == \
        {"data": 4, "model": 2}
    # bigger model: tp grows until the shard fits (20/4 = 5 GiB < budget)
    assert derive_mesh_spec(8, 20 * gib, hbm_bytes=16 * gib).shape == \
        {"data": 2, "model": 4}
    # enormous model: tp absorbs every chip before giving up
    assert derive_mesh_spec(8, 30 * gib, hbm_bytes=16 * gib).shape == \
        {"data": 1, "model": 8}
    # unknown catalog: stay dp-only
    assert derive_mesh_spec(8, None, hbm_bytes=16 * gib).shape == \
        {"data": 8, "model": 1}
    # odd device counts cannot split: dp-only even for big models
    assert derive_mesh_spec(3, 30 * gib, hbm_bytes=16 * gib).shape == \
        {"data": 3, "model": 1}
    # latency mode: leftover chips ride ``seq`` (ring attention) not dp
    assert derive_mesh_spec(8, 2 * gib, hbm_bytes=16 * gib,
                            latency=True).shape == \
        {"data": 1, "model": 1, "seq": 8}
    assert derive_mesh_spec(8, 7 * gib, hbm_bytes=16 * gib,
                            latency=True).shape == \
        {"data": 1, "model": 2, "seq": 4}
    # latency mode on one chip degenerates to the single-chip mesh
    assert derive_mesh_spec(1, 7 * gib, latency=True).shape == {"data": 1}
    # non-pow2 remainder: seq takes only the pow2 factor (it must divide
    # the pow2 spatial token counts or ring attention never engages);
    # the rest returns to data
    assert derive_mesh_spec(6, 2 * gib, hbm_bytes=16 * gib,
                            latency=True).shape == \
        {"data": 3, "model": 1, "seq": 2}
    assert derive_mesh_spec(3, 2 * gib, hbm_bytes=16 * gib,
                            latency=True).shape == \
        {"data": 3, "model": 1}


def test_split_mesh_partitions_devices():
    """split_mesh: contiguous, disjoint, covering data-axis submeshes —
    the substrate for the cascade's stage-level pipeline parallelism."""
    import jax
    import pytest

    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh, split_mesh

    mesh = build_mesh(MeshSpec({"data": -1}))
    halves = split_mesh(mesh, 2)
    assert len(halves) == 2
    seen = []
    for sub in halves:
        assert dict(sub.shape)["data"] == len(jax.devices()) // 2
        seen += sub.devices.flatten().tolist()
    assert seen == mesh.devices.flatten().tolist()  # disjoint AND ordered
    with pytest.raises(ValueError):
        split_mesh(mesh, 3)  # 8 devices do not split three ways


@pytest.mark.slow
def test_worker_default_pool_derives_tp_for_big_families(monkeypatch):
    """A stock 8-device worker with an SDXL-class catalog builds a
    dp=4 x tp=2 slot WITHOUT any hand-written mesh_shape; a small-model
    catalog stays dp=8 (VERDICT r2: the Megatron layer must not sit idle
    behind operator configuration)."""
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    # estimate_family_bytes traces full SDXL abstractly (seconds); pin the
    # HBM budget so the test is deterministic across backends
    from chiaswarm_tpu.core import mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "device_hbm_bytes",
                        lambda device=None: 16 * 1024**3)

    sdxl_reg = ModelRegistry(
        catalog=[{"name": "stabilityai/stable-diffusion-xl-base-1.0",
                  "family": "sdxl", "parameters": {}}],
        allow_random=True)
    worker = Worker(settings=Settings(hive_uri="http://x", hive_token="t"),
                    registry=sdxl_reg)
    shape = worker.pool.slots[0].descriptor()["mesh_shape"]
    assert shape == {"data": 4, "model": 2, "seq": 1}

    tiny_reg = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)
    worker2 = Worker(settings=Settings(hive_uri="http://x", hive_token="t"),
                     registry=tiny_reg)
    shape2 = worker2.pool.slots[0].descriptor()["mesh_shape"]
    assert shape2 == {"data": 8, "model": 1, "seq": 1}

    # latency_mode flips the leftover chips onto the ring-attention axis
    worker3 = Worker(settings=Settings(hive_uri="http://x", hive_token="t",
                                       latency_mode=True),
                     registry=tiny_reg)
    shape3 = worker3.pool.slots[0].descriptor()["mesh_shape"]
    assert shape3 == {"data": 1, "model": 1, "seq": 8}


def test_chip_pool_slots_and_seed_recording():
    pool = ChipPool(n_slots=4)
    assert len(pool) == 4
    slot = pool.slots[0]
    assert slot.descriptor()["chips"] == 2

    def callback(s, model_name, seed=None, **kw):
        assert model_name == "m"
        assert isinstance(seed, int)
        return {"ok": True}, {"model": model_name}

    artifacts, config = slot(callback, model_name="m")
    assert artifacts == {"ok": True}
    assert isinstance(config["seed"], int)

    _, config2 = slot(callback, model_name="m", seed=123)
    assert config2["seed"] == 123


def test_chip_pool_busy_raises_past_pipeline_depth():
    """Depth-1 slot == the reference's hard mutex; the default depth-2
    slot admits ONE extra in-flight job, then raises."""
    slot1 = ChipPool(n_slots=1, depth=1).slots[0]

    def reentrant(s, model_name, seed=None, **kw):
        with pytest.raises(SlotBusy):
            slot1(lambda *a, **k: ({}, {}))
        return {}, {}

    slot1(reentrant, model_name=None)

    slot2 = ChipPool(n_slots=1, depth=2).slots[0]

    def two_deep(s, model_name, seed=None, **kw):
        def inner(s2, model_name2, seed=None, **kw2):
            with pytest.raises(SlotBusy):  # third concurrent job: full
                slot2(lambda *a, **k: ({}, {}))
            return {}, {}

        slot2(inner, model_name=None)  # second concurrent job: admitted
        return {}, {}

    slot2(two_deep, model_name=None)


def test_rng_determinism():
    k1 = key_for_seed(42)
    k2 = key_for_seed(42)
    assert (jax.random.normal(k1, (4,)) == jax.random.normal(k2, (4,))).all()
    seeds = {draw_seed() for _ in range(8)}
    assert len(seeds) == 8
    keys = per_sample_keys(7, 3)
    assert keys.shape[0] == 3
    assert np.array_equal(np.asarray(keys[1]), np.asarray(key_for_seed(8)))


def test_bucketing():
    assert bucket_batch(1) == 1
    assert bucket_batch(3) == 4
    assert bucket_image_size(512, 512) == (512, 512)
    assert bucket_image_size(500, 700) == (512, 704)
    # small sizes are honored (reference has only a MAX clamp,
    # job_arguments.py:96-102); quantized up to the 64 lattice
    assert bucket_image_size(70, 60) == (128, 64)
    assert bucket_image_size(192, 192) == (192, 192)
    assert bucket_image_size(4000, 100) == (1024, 128)


def test_lru_cache_eviction_and_stats():
    cache = LruCache(max_items=2)
    cache.get_or_create("a", lambda: 1)
    cache.get_or_create("b", lambda: 2)
    cache.get_or_create("a", lambda: -1)  # hit, refreshes
    cache.get_or_create("c", lambda: 3)   # evicts b
    assert cache.get_or_create("a", lambda: -1) == 1
    assert cache.get_or_create("b", lambda: 99) == 99  # was evicted
    assert cache.stats["hits"] == 2

    budget = LruCache(budget_bytes=100)
    budget.get_or_create("x", lambda: "x", size_bytes=60)
    budget.get_or_create("y", lambda: "y", size_bytes=60)  # evicts x
    assert budget.stats["bytes"] == 60


def test_depth2_slot_runs_two_jobs_concurrently():
    """The serving overlap mechanism: two blocking jobs must be able to
    execute on ONE slot at the same time (each waits on a barrier only
    the other can release)."""
    import threading

    slot = ChipPool(n_slots=1, depth=2).slots[0]
    barrier = threading.Barrier(2, timeout=30)
    results = []

    def job(s, model_name, seed=None, **kw):
        barrier.wait()  # deadlocks unless both jobs are in flight
        return {}, {"ok": True}

    def run():
        results.append(slot(job, model_name=None))

    t1 = threading.Thread(target=run)
    t2 = threading.Thread(target=run)
    t1.start(); t2.start()
    t1.join(60); t2.join(60)
    assert len(results) == 2
    assert all(cfg["ok"] for _, cfg in results)
