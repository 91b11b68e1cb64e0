"""Cross-job coalescing: compatible txt2img jobs ride one batched program.

No reference analog — this is the dp-mesh efficiency path: a data-sharded
slot replicates a batch=1 job on every data row, so merging compatible
jobs into one batched program is what makes multi-chip slots earn their
chips (node/executor.py::synchronous_do_work_batch,
workloads/diffusion.py::diffusion_coalesced_callback). Per-sample
(seed, row) noise keys guarantee each job's images match its solo run.

Runs on the virtual 8-device CPU mesh (tests/conftest.py).
"""

from __future__ import annotations

import numpy as np
import pytest

from chiaswarm_tpu.core.chip_pool import ChipPool
from chiaswarm_tpu.core.mesh import MeshSpec
from chiaswarm_tpu.node.executor import (
    synchronous_do_work,
    synchronous_do_work_batch,
)
from chiaswarm_tpu.node.registry import ModelRegistry


@pytest.fixture()
def registry():
    return ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True,
    )


def _job(i: int, **over):
    job = {"id": f"j{i}", "model_name": "tiny", "prompt": f"prompt {i}",
           "seed": 100 + i, "num_inference_steps": 2,
           "height": 64, "width": 64, "content_type": "image/png"}
    job.update(over)
    return job


@pytest.mark.slow
def test_burst_coalesces_and_matches_solo(registry):
    """Three compatible jobs coalesce onto one program; each job's image
    agrees with its solo run (same seed) to uint8 quantization."""
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    slot = pool.slots[0]
    jobs = [_job(0), _job(1), _job(2)]
    results = synchronous_do_work_batch(jobs, slot, registry)
    assert [r["id"] for r in results] == ["j0", "j1", "j2"]
    for r in results:
        assert "fatal_error" not in r
        assert r["pipeline_config"]["coalesced"] == 3
        assert r["pipeline_config"]["seed"] in (100, 101, 102)

    import base64
    import io

    from PIL import Image

    solo = synchronous_do_work(_job(1), slot, registry)
    solo_img = np.asarray(Image.open(io.BytesIO(
        base64.b64decode(solo["artifacts"]["primary"]["blob"]))))
    co_img = np.asarray(Image.open(io.BytesIO(
        base64.b64decode(results[1]["artifacts"]["primary"]["blob"]))))
    diff = np.abs(co_img.astype(int) - solo_img.astype(int))
    # different compiled batch shapes: agreement to quantization, not bits
    assert diff.max() <= 3 and (diff <= 1).mean() > 0.99, (
        diff.max(), (diff <= 1).mean())


@pytest.mark.slow
def test_incompatible_jobs_run_separately(registry):
    """A burst with mixed static params: the two compatible jobs coalesce,
    the odd one (different steps) runs alone; all ids come back."""
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    slot = pool.slots[0]
    jobs = [_job(0), _job(1, num_inference_steps=3), _job(2)]
    results = synchronous_do_work_batch(jobs, slot, registry)
    by_id = {r["id"]: r for r in results}
    assert set(by_id) == {"j0", "j1", "j2"}
    assert by_id["j0"]["pipeline_config"]["coalesced"] == 2
    assert by_id["j2"]["pipeline_config"]["coalesced"] == 2
    assert "coalesced" not in by_id["j1"]["pipeline_config"]


@pytest.mark.slow
def test_mixed_mode_jobs_do_not_coalesce_with_each_other(registry):
    """txt2img and img2img in one burst: modes must not merge (different
    compiled programs) — each runs its own path."""
    rng = np.random.default_rng(0)
    init = rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    jobs = [_job(0), _job(1, image=init, strength=0.6)]
    results = synchronous_do_work_batch(jobs, pool.slots[0], registry)
    by_id = {r["id"]: r for r in results}
    assert "coalesced" not in by_id["j0"]["pipeline_config"]
    assert "coalesced" not in by_id["j1"]["pipeline_config"]
    assert by_id["j1"]["pipeline_config"]["mode"] == "img2img"


def _round_trip_image(result) -> np.ndarray:
    import base64
    import io

    from PIL import Image

    return np.asarray(Image.open(io.BytesIO(
        base64.b64decode(result["artifacts"]["primary"]["blob"]))))


@pytest.mark.slow
def test_img2img_jobs_coalesce_and_match_solo(registry):
    """VERDICT r4 #2: image-conditioned 512px-class jobs join the burst —
    per-job init stacks + per-job VAE-encode seeds keep every job's
    images equal to its solo run (to uint8 quantization across batch
    shapes)."""
    rng = np.random.default_rng(1)
    inits = [rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)
             for _ in range(3)]
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    slot = pool.slots[0]
    jobs = [_job(i, image=inits[i], strength=0.6) for i in range(3)]
    results = synchronous_do_work_batch(jobs, slot, registry)
    by_id = {r["id"]: r for r in results}
    for r in results:
        assert "fatal_error" not in r, r
        assert r["pipeline_config"]["coalesced"] == 3
        assert r["pipeline_config"]["mode"] == "img2img"

    solo = synchronous_do_work(_job(1, image=inits[1], strength=0.6),
                               slot, registry)
    assert solo["pipeline_config"]["mode"] == "img2img"
    diff = np.abs(_round_trip_image(by_id["j1"]).astype(int)
                  - _round_trip_image(solo).astype(int))
    assert diff.max() <= 3 and (diff <= 1).mean() > 0.99, (
        diff.max(), (diff <= 1).mean())


@pytest.mark.slow
def test_inpaint_jobs_coalesce_with_distinct_masks(registry):
    """Inpaint jobs with DIFFERENT masks ride one program: the mask is a
    per-row stack; each job's kept region comes from its own source."""
    rng = np.random.default_rng(2)
    inits = [rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)
             for _ in range(2)]
    masks = [np.zeros((64, 64), np.float32), np.zeros((64, 64), np.float32)]
    masks[0][:32] = 1.0          # regenerate top half
    masks[1][:, 32:] = 1.0       # regenerate right half
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    slot = pool.slots[0]
    jobs = [_job(i, image=inits[i], mask_image=masks[i], strength=0.8)
            for i in range(2)]
    results = synchronous_do_work_batch(jobs, slot, registry)
    by_id = {r["id"]: r for r in results}
    for r in results:
        assert "fatal_error" not in r, r
        assert r["pipeline_config"]["coalesced"] == 2
        assert r["pipeline_config"]["mode"] == "inpaint"

    solo = synchronous_do_work(
        _job(1, image=inits[1], mask_image=masks[1], strength=0.8),
        slot, registry)
    diff = np.abs(_round_trip_image(by_id["j1"]).astype(int)
                  - _round_trip_image(solo).astype(int))
    assert diff.max() <= 3 and (diff <= 1).mean() > 0.99, (
        diff.max(), (diff <= 1).mean())


@pytest.mark.slow
def test_burst_with_formatting_error_still_returns_all(registry):
    jobs = [_job(0), _job(1, height=9999, width=9999), _job(2)]
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    results = synchronous_do_work_batch(jobs, pool.slots[0], registry)
    by_id = {r["id"]: r for r in results}
    assert set(by_id) == {"j0", "j1", "j2"}
    assert by_id["j1"]["fatal_error"] is True
    assert by_id["j0"]["pipeline_config"]["coalesced"] == 2


@pytest.mark.slow
def test_worker_coalesces_queue_burst(registry):
    """Full worker loop on a dp=4 mesh slot: a burst of four compatible
    jobs arrives in one poll; the slot merges them into one program
    (every result reports coalesced=4)."""
    import asyncio
    import sys

    sys.path.insert(0, "tests")
    from fake_hive import FakeHive

    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    async def main():
        hive = FakeHive()
        await hive.start()
        for i in range(4):
            hive.jobs.append(_job(i))
        pool = ChipPool(n_slots=1,
                        mesh_spec=MeshSpec({"data": 4, "model": 2}))
        assert pool.slots[0].mesh.devices.size == 8
        worker = Worker(
            settings=Settings(hive_uri=hive.uri, hive_token="t",
                              worker_name="coalesce-test"),
            registry=registry, pool=pool)
        assert worker.work_queue.maxsize == 4  # data-axis capacity
        task = asyncio.create_task(worker.run())
        await hive.wait_for_results(4, timeout=300)
        worker.request_stop()
        try:
            await asyncio.wait_for(task, timeout=20)
        except asyncio.TimeoutError:
            task.cancel()
        await hive.stop()
        assert sorted(r["id"] for r in hive.results) == \
            ["j0", "j1", "j2", "j3"]
        merged = [r["pipeline_config"].get("coalesced")
                  for r in hive.results]
        # the poll delivers all four before the slot picks them up, so at
        # least some (normally all) coalesce; none may fail
        assert all(r["pipeline_config"].get("error") is None
                   for r in hive.results)
        assert any(m and m >= 2 for m in merged), merged

    asyncio.run(main())


def test_burst_key_prefilter(monkeypatch):
    """The worker's raw-job drain filter: txt2img/img2img/inpaint jobs
    with identical static fields share a burst key; modes never mix;
    cascade/controlnet/upscale/pix2pix stay per-job. Runs with lanes
    opted OUT — the strict per-field key is the pre-lane burst-path
    contract that CHIASWARM_STEPPER=0 must restore
    (test_stepper.py::test_burst_key_relaxes_only_with_stepper covers
    the lanes-on relaxation)."""
    from chiaswarm_tpu.node.worker import _burst_key

    monkeypatch.setenv("CHIASWARM_STEPPER", "0")
    a = _job(0)
    b = _job(1)
    assert _burst_key(a) is not None
    assert _burst_key(a) == _burst_key(b)
    assert _burst_key(_job(2, num_inference_steps=9)) != _burst_key(a)
    assert _burst_key(_job(3, workflow="txt2vid")) is None
    assert _burst_key(_job(5, model_name="DeepFloyd/IF-I-XL-v1.0")) is None
    assert _burst_key(
        _job(6, parameters={"controlnet": {"type": "canny"}})) is None
    assert _burst_key(_job(7, parameters={"upscale": True})) is None
    # img2img joins the drain (VERDICT r4 #2) but never mixes with
    # txt2img, other strengths, or inpaint
    i1 = _burst_key(_job(8, start_image_uri="http://x/i.png",
                         strength=0.6))
    i2 = _burst_key(_job(9, start_image_uri="http://x/other.png",
                         strength=0.6))
    assert i1 is not None and i1 == i2
    assert i1 != _burst_key(a)
    assert i1 != _burst_key(_job(10, start_image_uri="http://x/i.png",
                                 strength=0.9))
    assert i1 != _burst_key(_job(11, start_image_uri="http://x/i.png",
                                 mask_image_uri="http://x/m.png",
                                 strength=0.6))
    assert _burst_key(_job(12, model_name="timbrooks/instruct-pix2pix",
                           start_image_uri="http://x/i.png")) is None


def test_row_chunks_bounds_total_batch_rows():
    """num_images_per_prompt multiplies rows: 4 jobs x 8 images must NOT
    merge into one batch-32 program on a dp=4 slot (that is data_width
    times the per-device memory of any solo run); batch=1 jobs still
    coalesce up to data_width."""
    from chiaswarm_tpu.node.executor import _row_chunks

    def item(i, n):
        return (i, f"j{i}", "image/png", {"num_images_per_prompt": n})

    big = [item(i, 8) for i in range(4)]
    assert [len(c) for c in _row_chunks(big, 4)] == [1, 1, 1, 1]

    small = [item(i, 1) for i in range(4)]
    assert [len(c) for c in _row_chunks(small, 4)] == [4]

    # two n=2 jobs fit in one dp=4 program (4 rows); a third would not
    pairs = [item(i, 2) for i in range(3)]
    assert [len(c) for c in _row_chunks(pairs, 4)] == [2, 1]


def test_oversized_rows_run_per_job_not_batched(registry):
    """The per-device row budget guards the batch: 1024px-class jobs
    (single_chip_rows == 1) never merge past one solo footprint per
    device — pinned at the chunking layer, where the size class is the
    only input that matters. 512px-class jobs (budget 4/device) DO merge
    the same row counts (the r4 measured policy), covered end-to-end by
    test_single_chip_slot_batches_small_jobs."""
    from chiaswarm_tpu.node.executor import _row_chunks

    def item(i, n, size):
        return (i, f"j{i}", "image/png",
                {"num_images_per_prompt": n, "height": size, "width": size})

    big = [item(i, 4, 1024) for i in range(2)]
    assert [len(c) for c in _row_chunks(big, 4)] == [1, 1]
    small = [item(i, 4, 512) for i in range(2)]
    assert [len(c) for c in _row_chunks(small, 4)] == [2]
    # the budget is max(solo footprint, profitable batch), NOT their
    # product: a multi-image 512px job never multiplies into 4x its own
    # solo per-device memory
    multi = [item(i, 16, 512) for i in range(2)]
    assert [len(c) for c in _row_chunks(multi, 4)] == [1, 1]


@pytest.mark.slow
def test_oversized_rows_fall_back_per_job_e2e(registry):
    """End to end through synchronous_do_work_batch: jobs whose combined
    rows exceed the per-device budget run the per-job path — correct
    results, no 'coalesced' marker (the non-merging direction of the
    batching policy, e2e like its merging twin)."""
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    jobs = [_job(0, num_images_per_prompt=16),
            _job(1, num_images_per_prompt=16)]
    results = synchronous_do_work_batch(jobs, pool.slots[0], registry)
    by_id = {r["id"]: r for r in results}
    assert set(by_id) == {"j0", "j1"}
    for r in results:
        assert "coalesced" not in r["pipeline_config"]
        assert r["pipeline_config"].get("error") is None


def test_mismatched_job_keeps_fifo_position(monkeypatch):
    """The drain holds a non-matching candidate as the NEXT burst instead
    of re-queueing it at the tail (ADVICE r2): with queue
    [A, B, A2, A3] the mismatch B must execute before A2/A3 — the old
    tail re-queue ran [A, A2?]... and pushed B behind later arrivals.
    Lanes opted out: with the ISSUE-7 relaxed key the whole queue would
    drain as ONE burst and there would be no mismatch to hold."""
    import asyncio

    from chiaswarm_tpu.node import worker as worker_mod
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    monkeypatch.setenv("CHIASWARM_STEPPER", "0")

    class StubSlot:
        depth = 1          # serialize bursts so order is deterministic
        data_width = 4

        def descriptor(self):
            return "stub"

    class StubPool(list):
        pass

    bursts: list[list[str]] = []

    async def fake_do_work(job, slot, registry):
        bursts.append([job["id"]])
        return {"id": job["id"], "artifacts": {}, "pipeline_config": {}}

    async def fake_do_work_batch(jobs, slot, registry):
        bursts.append([j["id"] for j in jobs])
        return [{"id": j["id"], "artifacts": {}, "pipeline_config": {}}
                for j in jobs]

    monkeypatch.setattr(worker_mod, "do_work", fake_do_work)
    monkeypatch.setattr(worker_mod, "do_work_batch", fake_do_work_batch)

    async def main():
        pool = StubPool([StubSlot()])
        worker = Worker(
            settings=Settings(hive_uri="http://unused", hive_token="t",
                              worker_name="fifo-test"),
            registry=object(), pool=pool, hive=object())
        jobs = [_job(0), _job(1, num_inference_steps=3),
                _job(2), _job(3)]
        for job in jobs:
            worker.work_queue.put_nowait(job)
        task = asyncio.create_task(worker._slot_worker(pool[0]))
        await asyncio.wait_for(worker.work_queue.join(), timeout=30)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)

    asyncio.run(main())
    flat = [i for burst in bursts for i in burst]
    # j1 (the mismatch) runs immediately after the burst that found it,
    # NOT behind j2/j3
    assert flat == ["j0", "j1", "j2", "j3"], bursts
    assert bursts[1] == ["j1"], bursts
    # the compatible tail pair still coalesces after the held job ran
    assert ["j2", "j3"] in bursts, bursts


def test_multislot_pool_coalesces_with_fairness_reserve(monkeypatch):
    """VERDICT r2 weak #7: coalescing must also fire on multi-slot pools.
    Two dp=4 slots, four compatible jobs queued while BOTH slots wait:
    the first slot's drain leaves the fairness reserve (one job for the
    hungry neighbor) instead of stripping the whole queue — so the burst
    coalesces AND the second slot still gets work."""
    import asyncio

    from chiaswarm_tpu.node import worker as worker_mod
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    class StubSlot:
        depth = 1
        data_width = 4

        def __init__(self, name):
            self.name = name

        def descriptor(self):
            return self.name

    bursts: list[tuple[str, list[str]]] = []

    async def fake_do_work(job, slot, registry):
        bursts.append((slot.name, [job["id"]]))
        return {"id": job["id"], "artifacts": {}, "pipeline_config": {}}

    async def fake_do_work_batch(jobs, slot, registry):
        bursts.append((slot.name, [j["id"] for j in jobs]))
        return [{"id": j["id"], "artifacts": {}, "pipeline_config": {}}
                for j in jobs]

    monkeypatch.setattr(worker_mod, "do_work", fake_do_work)
    monkeypatch.setattr(worker_mod, "do_work_batch", fake_do_work_batch)

    async def main():
        pool = [StubSlot("s0"), StubSlot("s1")]
        worker = Worker(
            settings=Settings(hive_uri="http://unused", hive_token="t",
                              worker_name="multislot-test"),
            registry=object(), pool=pool, hive=object())
        tasks = [asyncio.create_task(worker._slot_worker(s)) for s in pool]
        for _ in range(5):  # let both slots block on work_queue.get()
            await asyncio.sleep(0)
        assert worker._hungry_slots == 2
        for i in range(4):
            worker.work_queue.put_nowait(_job(i))
        await asyncio.wait_for(worker.work_queue.join(), timeout=30)
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    asyncio.run(main())
    ran = sorted(i for _, burst in bursts for i in burst)
    assert ran == ["j0", "j1", "j2", "j3"], bursts
    sizes = sorted(len(burst) for _, burst in bursts)
    # coalescing fired on a multi-slot pool...
    assert sizes[-1] >= 2, bursts
    # ...but no slot drained everything: both slots executed work
    assert len({name for name, _ in bursts}) == 2, bursts


@pytest.mark.slow
def test_coalesced_default_content_type_is_png(registry):
    """Solo-equivalence of encoding: a job without content_type must come
    back PNG from the coalesced path (the solo callback's default), not
    the executor's jpeg error default."""
    import base64

    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 4, "model": 2}))
    jobs = []
    for i in range(2):
        job = _job(i)
        job.pop("content_type")
        jobs.append(job)
    results = synchronous_do_work_batch(jobs, pool.slots[0], registry)
    for r in results:
        assert r["pipeline_config"]["coalesced"] == 2
        assert r["artifacts"]["primary"]["content_type"] == "image/png"
        raw = base64.b64decode(r["artifacts"]["primary"]["blob"])
        assert raw.startswith(b"\x89PNG")
        # per-job throughput keeps solo semantics; program total reported
        # separately
        cfg = r["pipeline_config"]
        assert cfg["batch_images_per_sec"] >= cfg["images_per_sec"]


@pytest.mark.slow
def test_single_chip_slot_batches_small_jobs(registry):
    """A data_width=1 slot merges 512px-class jobs into one batched
    program (four rows a device up to 512 x 512 px; 1024px-class jobs
    stay one row per device). The rule is unverified on this chip:
    core/compile_cache.py::single_chip_rows."""
    from chiaswarm_tpu.core.compile_cache import single_chip_rows

    assert single_chip_rows({"height": 512, "width": 512}) == 4
    assert single_chip_rows({"height": 64, "width": 64}) == 4
    assert single_chip_rows({"height": 1024, "width": 1024}) == 1
    assert single_chip_rows({"height": None, "width": None}) == 1

    import jax

    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])
    assert pool.slots[0].data_width == 1
    jobs = [_job(i) for i in range(4)]
    results = synchronous_do_work_batch(jobs, pool.slots[0], registry)
    assert len(results) == 4
    assert all(r["pipeline_config"].get("error") is None for r in results)
    merged = [r["pipeline_config"].get("coalesced") for r in results]
    assert merged == [4, 4, 4, 4], merged


def test_coalesce_key_splits_mismatched_image_and_mask_grids():
    """The executor's grouping key must carry the fetched image AND mask
    shapes: free-form mask sizes are valid solo (the pipeline resizes),
    so keying on presence alone would group unstackable per-job masks
    and silently demote the burst to per-job execution."""
    from chiaswarm_tpu.node.executor import _coalesce_key

    img64 = np.zeros((64, 64, 3), np.uint8)
    img96 = np.zeros((96, 64, 3), np.uint8)
    m64 = np.zeros((64, 64), np.float32)
    m32 = np.zeros((32, 32), np.float32)
    base = {"model_name": "tiny", "num_inference_steps": 2,
            "strength": 0.6}
    k_a = _coalesce_key({**base, "image": img64, "mask_image": m64})
    k_b = _coalesce_key({**base, "image": img64, "mask_image": m64})
    assert k_a == k_b
    # different mask grid -> different group
    assert k_a != _coalesce_key({**base, "image": img64,
                                 "mask_image": m32})
    # different image grid -> different group
    assert k_a != _coalesce_key({**base, "image": img96,
                                 "mask_image": m64})
    # img2img vs inpaint -> different group
    assert k_a != _coalesce_key({**base, "image": img64})
    # strength is a static (schedule start index) -> different group
    assert _coalesce_key({**base, "image": img64}) != _coalesce_key(
        {**base, "image": img64, "strength": 0.9})
