"""Continuous step-level batching (serving/stepper.py): the numerical
equivalence gate plus the scheduling invariants.

Gate (ISSUE 3): a row denoised through a mixed-progress lane — spliced in
at a nonzero lane step, padded neighbors, per-row timesteps/sigmas,
DIFFERENT step counts and guidance scales sharing one program — must
match the solo per-job path for every sampler kind tier-1 serves
(dpmpp_2m, euler, euler_ancestral; DDIM/Heun/LMS map onto euler in this
framework, schedulers/sampling.py::SAMPLERS). Admission must never
compile (lane-program count bounded by buckets), deadlines apply per
row, and a failed lane bounces jobs to the per-job path instead of
losing them.

Runs on the hermetic CPU platform (tests/conftest.py).
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from chiaswarm_tpu.core.compile_cache import GLOBAL_CACHE
from chiaswarm_tpu.pipelines import (
    Components,
    DiffusionPipeline,
    GenerateRequest,
)
from chiaswarm_tpu.serving.stepper import (
    LaneDeadline,
    LaneReject,
    StepScheduler,
    aggregate_stats,
    stepper_enabled,
)


@pytest.fixture(scope="module")
def tiny_pipe():
    return DiffusionPipeline(Components.random("tiny", seed=0))


def _wait_steps(sched: StepScheduler, n: int, timeout: float = 120.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if sched.stats().get("steps_executed", 0) >= n:
            return
        time.sleep(0.005)
    raise AssertionError(
        f"scheduler never reached {n} steps: {sched.stats()}")


def _close(lane_img: np.ndarray, solo_img: np.ndarray) -> None:
    # different compiled batch shapes: agreement to uint8 quantization,
    # not bits (same tolerance as the burst-coalescing gate)
    diff = np.abs(lane_img.astype(int) - solo_img.astype(int))
    assert diff.max() <= 3 and (diff <= 1).mean() > 0.99, (
        diff.max(), (diff <= 1).mean())


# one representative per sampler KIND in the framework (the hive's other
# class names resolve onto these three, schedulers/sampling.py::SAMPLERS)
KINDS = [None,                                # -> dpmpp_2m (default)
         "DDIMScheduler",                     # -> euler family
         "EulerAncestralDiscreteScheduler"]   # -> euler_ancestral


@pytest.mark.parametrize("scheduler", KINDS)
def test_spliced_row_matches_solo(tiny_pipe, scheduler):
    """THE gate: job B splices into job A's running lane at a nonzero
    step, with a different step count AND guidance scale, and both jobs'
    images match their solo runs."""
    sched = StepScheduler()
    base = sched.stats().get("steps_executed", 0)
    fa = sched.submit_request(
        tiny_pipe, prompt="slow job", steps=16, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=21, scheduler=scheduler)
    _wait_steps(sched, base + 1)
    fb = sched.submit_request(
        tiny_pipe, prompt="late arrival", steps=3, guidance_scale=5.0,
        height=64, width=64, rows=1, seed=22, scheduler=scheduler)
    pending_b, info_b = fb.result(timeout=300)
    pending_a, info_a = fa.result(timeout=300)
    img_a, img_b = pending_a.wait(), pending_b.wait()
    # same lane, genuinely mid-flight: B joined after A had stepped
    assert info_b["lane"] == info_a["lane"]
    assert 1 <= info_b["admitted_at_step"] < 16

    solo_a, _ = tiny_pipe(GenerateRequest(
        prompt="slow job", steps=16, guidance_scale=7.5, height=64,
        width=64, seed=21, scheduler=scheduler))
    solo_b, _ = tiny_pipe(GenerateRequest(
        prompt="late arrival", steps=3, guidance_scale=5.0, height=64,
        width=64, seed=22, scheduler=scheduler))
    _close(img_a, solo_a)
    _close(img_b, solo_b)


def test_multi_row_job_matches_solo_batch(tiny_pipe):
    """num_images_per_prompt rows ride adjacent lane slots and match the
    solo batched run row-for-row (per-row fold_in keys)."""
    sched = StepScheduler()
    fut = sched.submit_request(
        tiny_pipe, prompt="pair", steps=4, guidance_scale=6.0,
        height=64, width=64, rows=2, seed=33)
    pending, _ = fut.result(timeout=300)
    imgs = pending.wait()
    solo, _ = tiny_pipe(GenerateRequest(
        prompt="pair", steps=4, guidance_scale=6.0, height=64, width=64,
        batch=2, seed=33))
    assert imgs.shape == solo.shape == (2, 64, 64, 3)
    _close(imgs, solo)


def test_admission_never_compiles(tiny_pipe, monkeypatch):
    """No recompile per admitted row: once a lane bucket is warm, jobs
    with new step counts / guidance values / seeds reuse the same four
    executables (the bounded-program acceptance criterion). Width is
    PINNED here so the adaptive controller cannot resize mid-test — a
    resize legitimately compiles the new lattice width once
    (test_adaptive_resize_compiles_only_new_lattice_widths covers
    that bound)."""
    monkeypatch.setenv("CHIASWARM_STEPPER_LANE_WIDTH", "4")
    sched = StepScheduler()
    sched.submit_request(tiny_pipe, prompt="warm", steps=5,
                         guidance_scale=7.5, height=64, width=64,
                         rows=1, seed=1).result(timeout=300)
    before = GLOBAL_CACHE.executables.stats["misses"]
    futs = [sched.submit_request(
        tiny_pipe, prompt=f"job {i}", steps=steps, guidance_scale=g,
        height=64, width=64, rows=1, seed=100 + i)
        for i, (steps, g) in enumerate([(4, 3.0), (7, 9.5), (9, 5.5)])]
    for fut in futs:
        fut.result(timeout=300)[0].wait()
    after = GLOBAL_CACHE.executables.stats["misses"]
    assert after == before, (before, after)


def test_row_deadline_expires_in_lane(tiny_pipe):
    """Per-row deadlines: an expired row retires with LaneDeadline while
    the lane keeps serving (the executor maps this to a structured
    timeout envelope, node/executor.py::_stepper_collect)."""
    sched = StepScheduler()
    fut = sched.submit_request(
        tiny_pipe, prompt="doomed", steps=8, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=5, deadline_s=0.0)
    with pytest.raises(LaneDeadline):
        fut.result(timeout=300)
    stats = sched.stats()
    assert stats.get("rows_expired", 0) >= 1
    # the lane survives: a follow-up job still completes
    ok = sched.submit_request(
        tiny_pipe, prompt="fine", steps=2, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=6)
    ok.result(timeout=300)[0].wait()


def test_lane_rejects_out_of_policy_jobs(tiny_pipe):
    sched = StepScheduler()
    with pytest.raises(LaneReject):  # no-CFG jobs run the solo program
        sched.submit_request(tiny_pipe, prompt="x", steps=4,
                             guidance_scale=1.0, height=64, width=64,
                             rows=1, seed=1)
    with pytest.raises(LaneReject):  # steps beyond the capacity lattice
        sched.submit_request(tiny_pipe, prompt="x", steps=4000,
                             guidance_scale=7.5, height=64, width=64,
                             rows=1, seed=1)
    with pytest.raises(LaneReject):  # wider than the lane
        sched.submit_request(tiny_pipe, prompt="x", steps=4,
                             guidance_scale=7.5, height=64, width=64,
                             rows=128, seed=1)


def test_injected_fault_bounces_rows_not_loses_them(tiny_pipe):
    """A lane fault (chaos seam) fails every resident row's future — the
    zero-loss contract is 'exception, never silence'."""
    sched = StepScheduler()
    boom = RuntimeError("RESOURCE_EXHAUSTED: injected mid-lane")
    sched.inject_fault(after_steps=sched.stats().get("steps_executed", 0),
                       exc=boom)
    fut = sched.submit_request(
        tiny_pipe, prompt="unlucky", steps=6, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=9)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        fut.result(timeout=300)
    assert sched.stats().get("lanes_failed", 0) >= 1
    # the scheduler opens a FRESH lane afterwards and serves again
    ok = sched.submit_request(
        tiny_pipe, prompt="retry", steps=2, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=10)
    ok.result(timeout=300)[0].wait()


def test_oom_halves_width_even_after_lane_teardown(tiny_pipe):
    """The degradation ladder survives the teardown race: by the time a
    collector classifies the failure as OOM and calls note_oom(), the
    dead lane is already deregistered — the recorded failure hint must
    still let the halving find its key, and it must fire ONCE per
    incident no matter how many resident jobs report it."""
    sched = StepScheduler()
    sched.inject_fault(after_steps=sched.stats().get("steps_executed", 0),
                       exc=RuntimeError("RESOURCE_EXHAUSTED: oom"))
    fut = sched.submit_request(
        tiny_pipe, prompt="oomed", steps=4, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=40)
    with pytest.raises(RuntimeError):
        fut.result(timeout=300)
    for _ in range(3):  # every resident job's collector reports it
        sched.note_oom()
    assert sched._width_limits, "halving lost the dead lane's key"
    (limit,) = set(sched._width_limits.values())
    # halved exactly once from the width the dead lane actually ran at
    # (adaptive lanes open at initial_width, not the saturation anchor)
    assert limit == max(1, sched.initial_width(1, 64, 64) // 2)
    # the rebuilt lane honors the limit and still serves
    ok = sched.submit_request(
        tiny_pipe, prompt="after", steps=2, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=41)
    ok.result(timeout=300)[0].wait()


def test_drain_and_shutdown_retire_lanes(tiny_pipe):
    # contention probe (ISSUE 18 deflake, the PR-12/PR-17 pattern): on
    # an oversubscribed CI host the lane thread can hold the step loop
    # through a GIL-contended device sync, so the FIXED 5 s default
    # lane.join inside shutdown() can return with the thread still
    # live and lanes_live lands on a stale nonzero. Sample host
    # contention across the drain and widen the join deadline by the
    # measured factor; on a quiet host the factor is 1.0 and the
    # deadline is unchanged.
    from chiaswarm_tpu.node.loadgen import ContentionProbe

    probe = ContentionProbe().start()
    sched = StepScheduler()
    fut = sched.submit_request(
        tiny_pipe, prompt="drainee", steps=6, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=11)
    assert sched.drain(timeout_s=300.0)
    assert fut.done()
    fut.result()[0].wait()
    sched.shutdown(timeout_s=5.0 * probe.stop())
    assert sched.stats()["lanes_live"] == 0


def test_stats_and_aggregation(tiny_pipe):
    sched = StepScheduler()
    fut = sched.submit_request(
        tiny_pipe, prompt="counted", steps=4, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=12)
    fut.result(timeout=300)[0].wait()
    stats = sched.stats()
    assert stats["rows_admitted"] >= 1
    assert stats["steps_executed"] >= 4
    assert abs(stats["lane_occupancy"] + stats["padding_waste"] - 1.0) < 1e-6
    merged = aggregate_stats([sched, StepScheduler()])
    assert merged["rows_admitted"] == stats["rows_admitted"]
    assert 0.0 <= merged["lane_occupancy"] <= 1.0


# ---- executor wiring (node/executor.py) --------------------------------


@pytest.fixture()
def registry():
    from chiaswarm_tpu.node.registry import ModelRegistry

    return ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True,
    )


def _job(i: int, **over):
    job = {"id": f"s{i}", "model_name": "tiny", "prompt": f"prompt {i}",
           "seed": 200 + i, "num_inference_steps": 2,
           "height": 64, "width": 64, "content_type": "image/png"}
    job.update(over)
    return job


@pytest.fixture()
def single_chip_slot():
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])
    return pool.slots[0]


def test_executor_routes_mixed_steps_onto_one_lane(
        monkeypatch, registry, single_chip_slot):
    """The relaxed admission key: jobs differing in steps AND guidance —
    which the burst path refuses to merge — share one lane program."""
    from chiaswarm_tpu.node.executor import synchronous_do_work_batch

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    assert stepper_enabled()
    # s0/s3 share a step count on purpose: two DISTINCT jobs retiring at
    # the same boundary once bounced every row with "truth value of an
    # array is ambiguous" (dataclass field-eq on device arrays during
    # the membership check) — keep that shape covered
    jobs = [_job(0, num_inference_steps=2),
            _job(1, num_inference_steps=3, guidance_scale=5.0),
            _job(2, num_inference_steps=4),
            _job(3, num_inference_steps=2)]
    results = synchronous_do_work_batch(jobs, single_chip_slot, registry)
    by_id = {r["id"]: r for r in results}
    assert set(by_id) == {"s0", "s1", "s2", "s3"}
    lanes = set()
    for r in results:
        cfg = r["pipeline_config"]
        assert cfg.get("error") is None, cfg
        assert "stepper" in cfg, cfg
        assert cfg["seed"] in (200, 201, 202, 203)
        lanes.add(cfg["stepper"]["lane"])
    assert len(lanes) == 1, lanes
    stats = single_chip_slot._stepper.stats()
    assert stats["rows_completed"] >= 4


def test_executor_stepper_matches_solo_path(
        monkeypatch, registry, single_chip_slot):
    """End-to-end solo equivalence through the executor: the same job
    with lanes on and off produces the same image."""
    from chiaswarm_tpu.node.executor import synchronous_do_work

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    lane_res = synchronous_do_work(_job(7, num_inference_steps=3),
                                   single_chip_slot, registry)
    assert "stepper" in lane_res["pipeline_config"]
    monkeypatch.setenv("CHIASWARM_STEPPER", "0")
    solo_res = synchronous_do_work(_job(7, num_inference_steps=3),
                                   single_chip_slot, registry)
    assert "stepper" not in solo_res["pipeline_config"]

    import base64
    import io

    from PIL import Image

    def img(res):
        return np.asarray(Image.open(io.BytesIO(base64.b64decode(
            res["artifacts"]["primary"]["blob"]))))

    _close(img(lane_res), img(solo_res))


def test_executor_falls_back_when_lane_faults(
        monkeypatch, registry, single_chip_slot):
    """Zero-loss through the executor: a faulted lane run falls back to
    the per-job path and the job still succeeds."""
    from chiaswarm_tpu.node.executor import synchronous_do_work
    from chiaswarm_tpu.serving.stepper import get_stepper

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    stepper = get_stepper(single_chip_slot)
    stepper.inject_fault(
        after_steps=stepper.stats().get("steps_executed", 0),
        exc=RuntimeError("chaos: mid-lane crash"))
    result = synchronous_do_work(_job(9, num_inference_steps=3),
                                 single_chip_slot, registry)
    cfg = result["pipeline_config"]
    assert cfg.get("error") is None, cfg
    assert "stepper" not in cfg  # served by the fallback path
    assert "fatal_error" not in result


def test_executor_ineligible_jobs_keep_burst_path(
        monkeypatch, registry, single_chip_slot):
    """The lane-ineligible residue keeps its solo/burst programs:
    no-CFG jobs (the solo path compiles the no-CFG program) and upscale
    passes never enter lanes — while img2img, eligible since ISSUE 7,
    rides a lane and says so in its config stamp."""
    from chiaswarm_tpu.node.executor import synchronous_do_work

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    rng = np.random.default_rng(3)
    init = rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)
    r = synchronous_do_work(_job(11, image=init, strength=0.6),
                            single_chip_slot, registry)
    assert r["pipeline_config"]["mode"] == "img2img"
    assert "stepper" in r["pipeline_config"]  # lanes are the engine now
    r = synchronous_do_work(_job(12, guidance_scale=1.0),
                            single_chip_slot, registry)
    assert r["pipeline_config"].get("error") is None
    assert "stepper" not in r["pipeline_config"]


def test_executor_opt_out_restores_burst_routing(
        monkeypatch, registry, single_chip_slot):
    """CHIASWARM_STEPPER=0 restores the pre-lane routing end to end:
    even a perfectly eligible txt2img job runs its solo/burst program
    and carries no lane stamp (the ISSUE-7 opt-out acceptance gate)."""
    from chiaswarm_tpu.node.executor import synchronous_do_work

    monkeypatch.setenv("CHIASWARM_STEPPER", "0")
    r = synchronous_do_work(_job(13), single_chip_slot, registry)
    assert r["pipeline_config"].get("error") is None
    assert r["pipeline_config"]["mode"] == "txt2img"
    assert "stepper" not in r["pipeline_config"]


def test_burst_key_relaxes_only_with_stepper(monkeypatch):
    """Worker drain prefilter: steps/guidance/strength leave the burst
    key exactly when lanes are on (they ride per row) — since ISSUE 7
    for img2img and inpaint too, while the mode split itself stays."""
    from chiaswarm_tpu.node.worker import _burst_key

    monkeypatch.setenv("CHIASWARM_STEPPER", "0")
    assert _burst_key(_job(0)) != _burst_key(_job(1, num_inference_steps=9))
    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    assert _burst_key(_job(0)) == _burst_key(_job(1, num_inference_steps=9))
    assert _burst_key(_job(0)) == _burst_key(_job(2, guidance_scale=3.0))
    # image modes relax the per-row fields too (their lanes exist now:
    # strength is a per-row start index)...
    i1 = _burst_key(_job(3, start_image_uri="http://x/i.png",
                         num_inference_steps=2, strength=0.6))
    i2 = _burst_key(_job(4, start_image_uri="http://x/i.png",
                         num_inference_steps=9, strength=0.9))
    assert i1 is not None and i1 == i2
    # ...but never mix with txt2img or inpaint (the mode split holds)
    assert i1 != _burst_key(_job(0))
    assert i1 != _burst_key(_job(5, start_image_uri="http://x/i.png",
                                 mask_image_uri="http://x/m.png"))
    monkeypatch.setenv("CHIASWARM_STEPPER", "0")
    i3 = _burst_key(_job(6, start_image_uri="http://x/i.png",
                         num_inference_steps=2))
    i4 = _burst_key(_job(7, start_image_uri="http://x/i.png",
                         num_inference_steps=9))
    assert i3 != i4  # opt-out restores the strict image-mode keys


def test_worker_health_reports_stepper_counters(monkeypatch, registry,
                                                single_chip_slot):
    """/healthz: step-scheduler counters ride next to the resilience
    stats (lane occupancy, mid-flight admissions, steps executed)."""
    from chiaswarm_tpu.node.executor import synchronous_do_work
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    synchronous_do_work(_job(20, num_inference_steps=2),
                        single_chip_slot, registry)
    worker = Worker(
        settings=Settings(hive_uri="http://unused", hive_token="t",
                          worker_name="stepper-health"),
        registry=registry, pool=[single_chip_slot], hive=object())
    health = worker.health()
    stepper = health["stepper"]
    assert stepper["enabled"] is True
    assert stepper["rows_completed"] >= 1
    assert stepper["steps_executed"] >= 2
    assert 0.0 <= stepper["lane_occupancy"] <= 1.0


# ---- step-boundary checkpoint / resume (ISSUE 6) -----------------------


def test_pack_unpack_roundtrip_is_bit_exact():
    """Resume state crosses two JSON serializations (spool file ->
    heartbeat -> redelivered job); the array packing must be exact —
    float bits and PRNG key words alike."""
    from chiaswarm_tpu.serving.stepper import pack_array, unpack_array

    rng = np.random.default_rng(7)
    latents = rng.standard_normal((2, 8, 8, 4)).astype(np.float32)
    keys = rng.integers(0, 2**32, size=(2, 2), dtype=np.uint32)
    for arr in (latents, keys):
        spec = pack_array(arr)
        back = unpack_array(spec)
        assert back.dtype == arr.dtype and back.shape == arr.shape
        assert np.array_equal(back, arr)
    # and through an actual JSON round trip
    import json

    back = unpack_array(json.loads(json.dumps(pack_array(latents))))
    assert np.array_equal(back, latents)


class _SpoolSlot:
    """Slot stub carrying only what lanes read: a checkpoint spool."""

    data_width = 1

    def __init__(self, spool):
        self._checkpoint_spool = spool


def test_lane_checkpoint_then_resume_matches_uninterrupted_run(
        tiny_pipe, tmp_path, monkeypatch):
    """The resume equivalence gate: a job restarted from a mid-run lane
    checkpoint (restored latents + keys + multistep history, spliced in
    at step k) finishes with images IDENTICAL to the uninterrupted lane
    run — and its lane info carries the nonzero resume step the
    acceptance criterion asserts on."""
    from chiaswarm_tpu.node.resilience import CheckpointSpool

    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    spool = CheckpointSpool(tmp_path / "ckpt")
    sched = StepScheduler(_SpoolSlot(spool))

    fut = sched.submit_request(
        tiny_pipe, prompt="resume me", steps=6, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=77, job_id="ck-1")
    pending, info = fut.result(timeout=300)
    imgs_fresh = pending.wait()
    assert info["resume_step"] == 0  # the uninterrupted run
    assert sched.stats().get("checkpoints_written", 0) >= 1

    # the spool holds the LAST pre-completion snapshot (step k >= 1);
    # hand it to a fresh scheduler as a redelivered job would arrive
    ckpt = spool.load("ck-1")
    assert ckpt is not None and ckpt["kind"] == "lane"
    assert 1 <= ckpt["step"] < 6

    sched2 = StepScheduler()
    fut2 = sched2.submit_request(
        tiny_pipe, prompt="resume me", steps=6, guidance_scale=7.5,
        height=64, width=64, rows=1,
        seed=0,  # deliberately different: resume must not re-derive keys
        job_id="ck-1", resume=ckpt)
    pending2, info2 = fut2.result(timeout=300)
    imgs_resumed = pending2.wait()
    assert info2["resume_step"] == ckpt["step"] >= 1
    assert sched2.stats().get("rows_resumed", 0) == 1
    # bit-identical: same executables, same restored state
    assert np.array_equal(imgs_resumed, imgs_fresh)


def test_resume_validation_rejects_mismatch_and_restarts_clean(
        tiny_pipe, tmp_path, monkeypatch):
    """A checkpoint that does not match the job (tampered steps) or is
    corrupt is rejected loudly: the job still completes — from step 0 —
    and the rejection is counted."""
    from chiaswarm_tpu.node.resilience import CheckpointSpool

    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    spool = CheckpointSpool(tmp_path / "ckpt2")
    sched = StepScheduler(_SpoolSlot(spool))
    fut = sched.submit_request(
        tiny_pipe, prompt="tamper", steps=5, guidance_scale=7.0,
        height=64, width=64, rows=1, seed=11, job_id="tp-1")
    imgs_solo = fut.result(timeout=300)[0].wait()

    ckpt = spool.load("tp-1")
    assert ckpt is not None
    tampered = dict(ckpt)
    tampered["steps"] = 9  # claims a different job

    sched2 = StepScheduler()
    fut2 = sched2.submit_request(
        tiny_pipe, prompt="tamper", steps=5, guidance_scale=7.0,
        height=64, width=64, rows=1, seed=11, job_id="tp-1",
        resume=tampered)
    pending2, info2 = fut2.result(timeout=300)
    assert info2["resume_step"] == 0  # restarted clean
    assert sched2.stats().get("resumes_rejected", 0) == 1
    assert np.array_equal(pending2.wait(), imgs_solo)

    # corrupt payloads reject the same way (never crash the submit)
    garbage = dict(ckpt)
    garbage["x"] = {"dtype": "float32", "shape": [1], "b64": "!!!"}
    fut3 = sched2.submit_request(
        tiny_pipe, prompt="tamper", steps=5, guidance_scale=7.0,
        height=64, width=64, rows=1, seed=11, resume=garbage)
    pending3, info3 = fut3.result(timeout=300)
    assert info3["resume_step"] == 0
    assert pending3.wait().shape == (1, 64, 64, 3)

    # a keys array with the right row count but the wrong tail shape
    # must reject at VALIDATION — inside lane admission it would take
    # every co-resident job down via the containment seam
    from chiaswarm_tpu.serving.stepper import pack_array
    bad_keys = dict(ckpt)
    bad_keys["keys"] = pack_array(np.zeros((1, 7), np.uint32))
    fut4 = sched2.submit_request(
        tiny_pipe, prompt="tamper", steps=5, guidance_scale=7.0,
        height=64, width=64, rows=1, seed=11, resume=bad_keys)
    pending4, info4 = fut4.result(timeout=300)
    assert info4["resume_step"] == 0
    assert sched2.stats().get("resumes_rejected", 0) == 3

    # latents stepped under a different guidance must not splice in and
    # finish under this job's guidance (wrong image delivered as a
    # success) — a mixed-up checkpoint restarts clean instead
    wrong_guidance = dict(ckpt)
    wrong_guidance["guidance"] = 3.0
    fut5 = sched2.submit_request(
        tiny_pipe, prompt="tamper", steps=5, guidance_scale=7.0,
        height=64, width=64, rows=1, seed=11, resume=wrong_guidance)
    pending5, info5 = fut5.result(timeout=300)
    assert info5["resume_step"] == 0
    assert sched2.stats().get("resumes_rejected", 0) == 4


def test_phase_checkpoint_resume_is_filtered_not_rejected(
        monkeypatch, registry, single_chip_slot):
    """A redelivered job whose dead worker ran it SOLO carries a
    phase-kind marker, not lane state: the lane path must filter it
    silently (fresh start at step 0) — a routine redelivery, not the
    tamper/corruption signal ``resumes_rejected`` counts."""
    from chiaswarm_tpu.node.executor import synchronous_do_work

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    before = single_chip_slot._stepper.stats().get("resumes_rejected", 0) \
        if getattr(single_chip_slot, "_stepper", None) else 0
    result = synchronous_do_work(
        _job(30, num_inference_steps=2,
             resume={"version": 1, "kind": "phase", "phase": "denoised"}),
        single_chip_slot, registry)
    cfg = result["pipeline_config"]
    assert cfg.get("error") is None, cfg
    assert cfg["stepper"]["resume_step"] == 0
    stats = single_chip_slot._stepper.stats()
    assert stats.get("resumes_rejected", 0) == before  # NOT a rejection


def test_checkpoint_spool_hygiene(tmp_path):
    """ISSUE 6 satellite: per-worker namespacing, loud corrupt-file
    skip with a counter, GC on ack, wholesale clear at startup."""
    from chiaswarm_tpu.node.resilience import CheckpointSpool

    spool_a = CheckpointSpool(tmp_path / "checkpoints" / "worker-a")
    spool_b = CheckpointSpool(tmp_path / "checkpoints" / "worker-b")
    spool_a.save("j1", {"kind": "phase", "phase": "encoded"})
    spool_b.save("j1", {"kind": "phase", "phase": "denoised"})
    # namespaced: same job id, two workers, two files
    assert spool_a.load("j1")["phase"] == "encoded"
    assert spool_b.load("j1")["phase"] == "denoised"
    assert spool_a.depth() == spool_b.depth() == 1
    assert spool_a.written == 1

    # corrupt snapshot: skipped loudly, parked as .bad, counted
    path = spool_a.save("j2", {"kind": "lane", "step": 3})
    path.write_text("{truncated", encoding="utf-8")
    assert spool_a.load("j2") is None
    assert spool_a.corrupt_skipped == 1
    assert not path.exists()  # parked as .bad, not retried forever
    assert path.with_suffix(".json.bad").exists()

    # GC on ack removes exactly the acked job's file
    spool_a.save("j3", {"kind": "phase", "phase": "encoded"})
    spool_a.discard("j3")
    assert spool_a.load("j3") is None
    spool_a.discard("never-existed")  # idempotent

    # startup clear wipes leftovers (the hive's copies are authority),
    # including parked .bad corpses and orphaned mid-save .tmp files —
    # otherwise they accumulate forever across restarts
    spool_b.save("j4", {"kind": "phase", "phase": "encoded"})
    (spool_b.directory / "old.ckpt.json.tmp").write_text("{", "utf-8")
    assert spool_a.clear() >= 2            # j1 + the parked j2 .bad
    assert not list(spool_a.directory.glob("*.bad"))
    assert spool_b.clear() >= 2            # j4 + the orphaned .tmp
    assert spool_b.depth() == 0
    assert not list(spool_b.directory.glob("*.tmp"))


def test_checkpoint_spool_version_probe(tmp_path):
    """The heartbeat's has-it-changed probe must advance on EVERY save —
    including several within one filesystem-timestamp tick (coarse-mtime
    mounts), where an mtime-equality probe would report "unchanged" and
    leave a stale snapshot as the hive's resume authority."""
    from chiaswarm_tpu.node.resilience import CheckpointSpool

    spool = CheckpointSpool(tmp_path / "vers")
    assert spool.version("j1") is None  # absent
    spool.save("j1", {"kind": "lane", "step": 1})
    v1 = spool.version("j1")
    spool.save("j1", {"kind": "lane", "step": 2})  # same tick is fine
    v2 = spool.version("j1")
    assert v1 is not None and v2 is not None and v2 > v1
    spool.save("j2", {"kind": "phase", "phase": "encoded"})
    assert spool.version("j2") != spool.version("j1")
    spool.discard("j1")
    assert spool.version("j1") is None
    # a file this process never wrote (external checkpoint_dir) still
    # reads as present
    spool._path_for("ghost").write_text("{}", "utf-8")
    assert spool.version("ghost") == 0
    spool.clear()
    assert spool.version("j2") is None
    # distinct ids that sanitize identically ("job 1" vs "job_1") must
    # never collide onto one file — a collided checkpoint could resume
    # the OTHER job's latent trajectory
    spool.save("job 1", {"kind": "phase", "phase": "encoded"})
    spool.save("job_1", {"kind": "phase", "phase": "denoised"})
    assert spool.load("job 1")["phase"] == "encoded"
    assert spool.load("job_1")["phase"] == "denoised"
    assert spool.depth() == 2


def test_solo_path_records_phase_checkpoints(tmp_path):
    """The solo path's coarse markers (encoded -> denoised) ride the
    same spool through the executor's checkpoint scope; the file is
    GC'd on ack by the worker (covered in the spool hygiene test)."""
    from chiaswarm_tpu.node.resilience import (
        CheckpointSpool, checkpoint_scope, phase_checkpoint)

    spool = CheckpointSpool(tmp_path / "phases")
    phase_checkpoint("orphan")  # outside any scope: silent no-op
    assert spool.depth() == 0
    with checkpoint_scope(spool, "solo-1"):
        phase_checkpoint("encoded", model="tiny")
        assert spool.load("solo-1")["phase"] == "encoded"
        phase_checkpoint("denoised", model="tiny", generation_s=1.25)
    state = spool.load("solo-1")
    assert state["phase"] == "denoised"
    assert state["generation_s"] == 1.25
    # a None spool (stub slot, feature off) makes the scope a no-op
    with checkpoint_scope(None, "solo-2"):
        phase_checkpoint("encoded")
    assert spool.load("solo-2") is None


# ---------------------------------------------------------------------------
# ISSUE 7b: workload splice-equivalence gates (img2img / inpaint / ControlNet)
# ---------------------------------------------------------------------------


def _rng_image(seed: int, size: int = 64) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, (size, size, 3), dtype=np.uint8)


def _half_mask(size: int = 64) -> np.ndarray:
    mask = np.zeros((size, size), np.float32)
    mask[size // 2:] = 1.0
    return mask


def test_img2img_row_spliced_midflight_matches_solo(tiny_pipe):
    """ISSUE 7 gate: an img2img job (nonzero strength-derived start
    index) splices into a lane already mid-flight with a txt2img row,
    and BOTH match their solo runs — the per-row start index walks the
    identical truncated ladder."""
    init = _rng_image(70)
    sched = StepScheduler()
    base = sched.stats().get("steps_executed", 0)
    fa = sched.submit_request(
        tiny_pipe, prompt="resident txt2img", steps=16, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=71)
    _wait_steps(sched, base + 1)
    fb = sched.submit_request(
        tiny_pipe, prompt="late img2img", steps=6, guidance_scale=5.5,
        height=64, width=64, rows=1, seed=72,
        init_image=init, strength=0.5)
    pending_b, info_b = fb.result(timeout=300)
    pending_a, info_a = fa.result(timeout=300)
    img_a, img_b = pending_a.wait(), pending_b.wait()
    assert info_b["lane"] == info_a["lane"]  # one shared lane program
    assert 1 <= info_b["admitted_at_step"] < 16  # genuinely mid-flight
    sched.shutdown()

    solo_a, _ = tiny_pipe(GenerateRequest(
        prompt="resident txt2img", steps=16, guidance_scale=7.5,
        height=64, width=64, seed=71))
    solo_b, cfg_b = tiny_pipe(GenerateRequest(
        prompt="late img2img", steps=6, guidance_scale=5.5,
        height=64, width=64, seed=72, init_image=init, strength=0.5))
    assert cfg_b["mode"] == "img2img"
    assert cfg_b["denoise_steps"] < 6  # the truncated ladder engaged
    _close(img_a, solo_a)
    _close(img_b, solo_b)


def test_inpaint_row_spliced_midflight_matches_solo(tiny_pipe):
    """ISSUE 7 gate: an inpaint row (latent mask + clean source latents
    as lane row state, re-projected every step) admitted mid-flight
    matches its solo trajectory; the co-resident txt2img row is
    untouched by the inpaint math (per-row mask_on selection)."""
    init = _rng_image(75)
    mask = _half_mask()
    sched = StepScheduler()
    base = sched.stats().get("steps_executed", 0)
    fa = sched.submit_request(
        tiny_pipe, prompt="resident txt2img", steps=16, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=76)
    _wait_steps(sched, base + 1)
    fb = sched.submit_request(
        tiny_pipe, prompt="late inpaint", steps=5, guidance_scale=6.0,
        height=64, width=64, rows=1, seed=77,
        init_image=init, mask=mask)
    pending_b, info_b = fb.result(timeout=300)
    pending_a, info_a = fa.result(timeout=300)
    img_a, img_b = pending_a.wait(), pending_b.wait()
    assert info_b["lane"] == info_a["lane"]
    assert 1 <= info_b["admitted_at_step"] < 16
    sched.shutdown()

    solo_a, _ = tiny_pipe(GenerateRequest(
        prompt="resident txt2img", steps=16, guidance_scale=7.5,
        height=64, width=64, seed=76))
    solo_b, cfg_b = tiny_pipe(GenerateRequest(
        prompt="late inpaint", steps=5, guidance_scale=6.0,
        height=64, width=64, seed=77, init_image=init, mask=mask))
    assert cfg_b["mode"] == "inpaint"
    _close(img_a, solo_a)
    _close(img_b, solo_b)


def test_controlnet_rows_ride_bundle_keyed_lane_and_match_solo(tiny_pipe):
    """ISSUE 7 gate: ControlNet jobs ride a lane keyed by their bundle
    (per-row pre-embedded hints + conditioning scales), match the solo
    program, and never share a lane with plain txt2img rows."""
    from chiaswarm_tpu.pipelines.components import ControlNetBundle

    bundle = ControlNetBundle.random("tiny", seed=5)
    cond = _rng_image(80)
    sched = StepScheduler()
    fa = sched.submit_request(
        tiny_pipe, prompt="plain", steps=6, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=81)
    fb = sched.submit_request(
        tiny_pipe, prompt="controlled", steps=6, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=82,
        controlnet=bundle, control_image=cond, control_scale=0.8)
    pending_a, info_a = fa.result(timeout=300)
    pending_b, info_b = fb.result(timeout=300)
    img_a, img_b = pending_a.wait(), pending_b.wait()
    assert info_a["lane"] != info_b["lane"]  # bundle keys the lane
    sched.shutdown()

    solo_a, _ = tiny_pipe(GenerateRequest(
        prompt="plain", steps=6, guidance_scale=7.5, height=64, width=64,
        seed=81))
    solo_b, cfg_b = tiny_pipe(GenerateRequest(
        prompt="controlled", steps=6, guidance_scale=7.5, height=64,
        width=64, seed=82, controlnet=bundle, control_image=cond,
        control_scale=0.8))
    assert cfg_b.get("controlnet") is not None
    _close(img_a, solo_a)
    _close(img_b, solo_b)


def test_workload_admission_never_compiles_once_warm(tiny_pipe, monkeypatch):
    """The ISSUE-7 acceptance criterion for the new workloads: once the
    lane bucket (and the per-workload admission prep: init-latent
    encode, hint embed) is warm, admitting img2img / inpaint /
    ControlNet rows with new strengths, masks, scales and step counts
    compiles NOTHING — all per-row state, no per-job programs. Width is
    pinned so the adaptive controller cannot add lattice compiles."""
    from chiaswarm_tpu.pipelines.components import ControlNetBundle

    monkeypatch.setenv("CHIASWARM_STEPPER_LANE_WIDTH", "4")
    bundle = ControlNetBundle.random("tiny", seed=6)
    init, cond = _rng_image(85), _rng_image(86)
    sched = StepScheduler()
    # warm: one job per workload
    warm = [
        sched.submit_request(tiny_pipe, prompt="w1", steps=5,
                             guidance_scale=7.5, height=64, width=64,
                             rows=1, seed=1, init_image=init,
                             strength=0.6),
        sched.submit_request(tiny_pipe, prompt="w2", steps=5,
                             guidance_scale=7.5, height=64, width=64,
                             rows=1, seed=2, init_image=init,
                             mask=_half_mask()),
        sched.submit_request(tiny_pipe, prompt="w3", steps=5,
                             guidance_scale=7.5, height=64, width=64,
                             rows=1, seed=3, controlnet=bundle,
                             control_image=cond),
    ]
    for fut in warm:
        fut.result(timeout=300)[0].wait()
    before = GLOBAL_CACHE.executables.stats["misses"]
    checker = np.indices((64, 64)).sum(axis=0) % 2
    futs = [
        sched.submit_request(tiny_pipe, prompt="i2i", steps=7,
                             guidance_scale=4.0, height=64, width=64,
                             rows=1, seed=10, init_image=init,
                             strength=0.35),
        sched.submit_request(tiny_pipe, prompt="inp", steps=9,
                             guidance_scale=8.5, height=64, width=64,
                             rows=1, seed=11, init_image=init,
                             mask=checker.astype(np.float32)),
        sched.submit_request(tiny_pipe, prompt="ctl", steps=4,
                             guidance_scale=6.5, height=64, width=64,
                             rows=1, seed=12, controlnet=bundle,
                             control_image=_rng_image(87),
                             control_scale=0.3),
    ]
    for fut in futs:
        fut.result(timeout=300)[0].wait()
    after = GLOBAL_CACHE.executables.stats["misses"]
    sched.shutdown()
    assert after == before, (before, after)
    admitted = sched.stats()
    assert admitted.get("rows_admitted_img2img", 0) >= 2
    assert admitted.get("rows_admitted_inpaint", 0) >= 2
    assert admitted.get("rows_admitted_controlnet", 0) >= 2


def test_resume_rejects_workload_mismatch(tiny_pipe):
    """A checkpoint stepped down a different ladder suffix (txt2img from
    step 0) must not finish under an img2img job's identity — the
    workload/start fields are part of resume validation."""
    from chiaswarm_tpu.core.rng import key_for_seed
    from chiaswarm_tpu.serving.stepper import ResumeReject, pack_array

    lh, lw = tiny_pipe._latent_hw(64, 64)
    ch = tiny_pipe.c.family.vae.latent_channels
    template = np.asarray(key_for_seed(0))
    ck = {
        "kind": "lane", "step": 4, "steps": 6, "rows": 1,
        "height": 64, "width": 64, "guidance": 7.5,
        "workload": "txt2img", "start": 0,
        "x": pack_array(np.zeros((1, lh, lw, ch), np.float32)),
        "keys": pack_array(np.zeros((1,) + template.shape,
                                    template.dtype)),
        "old": pack_array(np.zeros((1, lh, lw, ch), np.float32)),
    }
    sched = StepScheduler()
    with pytest.raises(ResumeReject, match="workload mismatch"):
        sched._validate_resume(tiny_pipe, ck, steps=6, rows=1, height=64,
                               width=64, guidance=7.5, start=3,
                               workload="img2img")
    # the same payload IS valid for the txt2img identity it came from
    step, restored = sched._validate_resume(
        tiny_pipe, ck, steps=6, rows=1, height=64, width=64,
        guidance=7.5, start=0, workload="txt2img")
    assert step == 4 and set(restored) == {"x", "keys", "old"}


# ---------------------------------------------------------------------------
# ISSUE 7c: adaptive lane width — control-loop units + lane integration
# ---------------------------------------------------------------------------


def _recorded_lane_trace(start: int, seed: int, boundaries: int = 160):
    """The seeded (occupied share, waiting rows, arrival rate) sequence
    ``_PR26_DECISIONS`` was recorded on."""
    import random

    rng = random.Random(f"issue27:{start}:{seed}")
    out = []
    share = rng.random()
    for _ in range(boundaries):
        if rng.random() < 0.15:           # regime change
            share = rng.choice((0.0, 0.1, 0.2, 0.25, 0.3, 0.5, 0.75, 0.8,
                                1.0))
        pending = (rng.choice((0, 0, 0, 0, 1, 2, 5, 40))
                   if rng.random() < 0.2 else 0)
        rate = rng.choice((0.0, 0.5, 3.0))
        out.append((share, pending, rate))
    return out


#: (start width, seed) -> every (boundary, new width) PR 26's controller
#: decided on ``_recorded_lane_trace`` while the lane was 4 rows or wider
_PR26_DECISIONS = {
    (4, 0): ((0, 64), (6, 32), (23, 64), (26, 128), (36, 64), (42, 32), (48, 16), (54, 8), (60, 4), (66, 2)),
    (4, 1): ((11, 8), (17, 4), (25, 2)),
    (8, 0): ((5, 64), (11, 32), (14, 64), (25, 32), (31, 16), (55, 8), (71, 64), (77, 32), (83, 16), (91, 32), (97, 64), (103, 128), (153, 64), (159, 32)),
    (8, 1): ((9, 64), (15, 32), (21, 16), (25, 64), (44, 32), (50, 16), (56, 8), (78, 16), (84, 32), (88, 64), (99, 32), (104, 64), (110, 128), (142, 64), (150, 32), (156, 16)),
    (16, 0): ((6, 8), (23, 16), (27, 64), (33, 128), (54, 64), (60, 32), (68, 64), (76, 32), (81, 128), (131, 64), (137, 32), (143, 16), (156, 8)),
    (16, 1): ((10, 8), (16, 4), (18, 8), (23, 16), (53, 8), (59, 4), (61, 8), (86, 64), (92, 32), (113, 64), (114, 128), (151, 64), (157, 32)),
    (32, 0): ((5, 16), (12, 64), (26, 128), (39, 64), (45, 32), (48, 64), (54, 32), (57, 128), (75, 64), (153, 32), (159, 16)),
    (32, 1): ((40, 64), (49, 128), (58, 64), (74, 128), (104, 64), (114, 128), (132, 64), (138, 32), (144, 16), (150, 8), (156, 4)),
    (64, 0): ((5, 32), (15, 128), (94, 64), (116, 32)),
    (64, 1): ((5, 32), (11, 16), (17, 8), (23, 4), (29, 2)),
    (128, 0): ((5, 64), (8, 128), (15, 64), (21, 32), (27, 16), (32, 64), (38, 32), (50, 64), (78, 128), (94, 64), (100, 32), (109, 64), (115, 32), (121, 64), (128, 32), (134, 16), (140, 8), (153, 64), (159, 128)),
    (128, 1): ((17, 64), (27, 32), (31, 64), (41, 128), (120, 64), (126, 32), (142, 16), (149, 32), (155, 16)),
}


class TestLaneWidthController:
    """Pure host-arithmetic units for the closed loop (no lanes, no jax):
    grow under burst, shrink under trickle, patience gating, OOM width
    limits, and the never-evict-residents floor."""

    def _ctl(self, **over):
        from chiaswarm_tpu.serving.stepper import LaneWidthController

        kw = dict(min_width=1, max_width=16, patience=3)
        kw.update(over)
        return LaneWidthController(**kw)

    def test_grow_under_burst_is_immediate(self):
        # pending rows that cannot fit resize NOW, onto the pow2 bucket
        ctl = self._ctl()
        assert ctl.decide(2, 2, 3, rate=1.0) == 8  # need 5 -> bucket 8

    def test_burst_growth_respects_max_width(self):
        ctl = self._ctl(max_width=4)
        assert ctl.decide(2, 2, 30, rate=5.0) == 4

    def test_grow_under_sustained_occupancy_needs_arrivals(self):
        ctl = self._ctl(alpha=1.0, grow_at=0.9, patience=2)
        assert ctl.decide(4, 4, 0, rate=2.0) == 4   # patience not met
        assert ctl.decide(4, 4, 0, rate=2.0) == 8   # sustained + flowing
        ctl2 = self._ctl(alpha=1.0, grow_at=0.9, patience=2)
        ctl2.decide(4, 4, 0, rate=0.0)
        # a full lane with NO arrivals holds: growing buys nothing
        assert ctl2.decide(4, 4, 0, rate=0.0) == 4

    def test_shrink_under_trickle_needs_patience(self):
        ctl = self._ctl(patience=3)
        assert ctl.decide(8, 1, 0, rate=0.0) == 8
        assert ctl.decide(8, 1, 0, rate=0.0) == 8
        assert ctl.decide(8, 1, 0, rate=0.0) == 4  # patience met: halve
        # and the counter re-arms after the resize
        assert ctl.decide(4, 1, 0, rate=0.0) == 4

    def test_never_shrinks_with_rows_pending(self):
        ctl = self._ctl(patience=1)
        for _ in range(8):
            assert ctl.decide(8, 1, 1, rate=0.1) == 8

    def test_oom_width_limit_clamps_the_next_decision(self):
        # note_oom's halved cap arrives as max_width: applied on the
        # very next boundary, patience or not
        ctl = self._ctl()
        assert ctl.decide(8, 1, 0, rate=0.0, max_width=4) == 4

    def test_width_never_drops_below_resident_rows(self):
        # an OOM cap below current occupancy must NOT evict residents:
        # the floor is the bucket holding every occupied row
        ctl = self._ctl()
        assert ctl.decide(8, 5, 0, rate=0.0, max_width=2) == 8

    # ---- ISSUE 27: the width follows the rows there is evidence for ----

    @pytest.mark.parametrize("patience", [3, 6])
    def test_lone_row_at_width_2_returns_to_1_after_patience(self, patience):
        # one row of two is a share of 0.5: shrink_at (0.25) can never
        # hold while it is resident, so at width 2 the rows decide —
        # after ``patience`` boundaries of fitting width 1, not sooner
        ctl = self._ctl(patience=patience)
        for _ in range(patience - 1):
            assert ctl.decide(2, 1, 0, rate=1.0) == 2
        assert ctl.decide(2, 1, 0, rate=1.0) == 1
        # re-armed: the next decisions at width 1 hold
        assert ctl.decide(1, 1, 0, rate=1.0) == 1

    def test_second_row_seen_restarts_the_count_to_1(self):
        # a waiting (pending or hinted) row is evidence for width 2:
        # the count of fitting boundaries starts over after it
        ctl = self._ctl(patience=3)
        assert ctl.decide(2, 1, 0, rate=0.0) == 2
        assert ctl.decide(2, 1, 0, rate=0.0) == 2
        assert ctl.decide(2, 1, 1, rate=0.0) == 2   # a row is waiting
        assert ctl.decide(2, 2, 0, rate=0.0) == 2   # ... and admitted
        assert ctl.decide(2, 1, 0, rate=0.0) == 2   # alone again: 1 of 3
        assert ctl.decide(2, 1, 0, rate=0.0) == 2
        assert ctl.decide(2, 1, 0, rate=0.0) == 1

    def test_lone_row_at_width_1_never_doubles_on_occupancy(self):
        # one row of one is a share of 1.0 >= grow_at whatever the
        # traffic: a lane that never held two rows has no evidence that
        # arrivals overlap, so only rows it can see widen it
        ctl = self._ctl(patience=2)
        for _ in range(40):
            assert ctl.decide(1, 1, 0, rate=5.0) == 1
        assert ctl.decide(1, 1, 1, rate=5.0) == 2   # a row it can see

    @pytest.mark.parametrize("width,occupied,k", [
        (1, 1, 1), (1, 1, 2), (1, 0, 3), (1, 1, 7), (2, 1, 2),
        (2, 2, 5), (4, 4, 1), (8, 3, 30)])
    def test_burst_of_k_rows_jumps_straight_to_their_bucket(
            self, width, occupied, k):
        from chiaswarm_tpu.core.compile_cache import bucket_batch

        ctl = self._ctl(max_width=128)
        assert ctl.decide(width, occupied, k, rate=1.0) == \
            bucket_batch(occupied + k)

    def test_two_rows_of_four_hold_width_4(self):
        # the rows rule is for widths where shrink_at is under one row
        # only: at width 4 two rows fit width 2, and the share rule
        # (0.5 > 0.25) keeps the lane as PR 26 did
        ctl = self._ctl(patience=2, alpha=1.0)
        for _ in range(40):
            assert ctl.decide(4, 2, 0, rate=0.0) == 4

    @pytest.mark.parametrize("start,seed", sorted(_PR26_DECISIONS))
    def test_decisions_at_widths_4_to_128_are_pr26s(self, start, seed):
        """Recorded from commit 370c296 (PR 26) with the shipped gains,
        bounds 1..128: every resize of a seeded occupancy / pending /
        rate sequence, up to the first width under 4."""
        from chiaswarm_tpu.serving.stepper import LaneWidthController

        ctl = LaneWidthController(min_width=1, max_width=128)
        width, changes = start, []
        for b, (share, pending, rate) in enumerate(
                _recorded_lane_trace(start, seed)):
            if width < 4:
                break
            new = ctl.decide(width, min(width, int(round(share * width))),
                             pending, rate)
            if new != width:
                changes.append((b, new))
                width = new
        assert tuple(changes) == _PR26_DECISIONS[(start, seed)]


def test_adaptive_lane_grows_midflight_and_rows_stay_solo_exact(
        tiny_pipe, monkeypatch):
    """Lane integration for the closed loop: a lane opened narrow grows
    at a step boundary when a burst cannot fit — never mid-step — and
    the resident row's trajectory survives the resize (device state
    compaction) bit-compatibly with its solo run."""
    monkeypatch.delenv("CHIASWARM_STEPPER_LANE_WIDTH", raising=False)
    monkeypatch.setenv("CHIASWARM_STEPPER_MIN_WIDTH", "2")
    sched = StepScheduler()
    base = sched.stats().get("steps_executed", 0)
    fa = sched.submit_request(
        tiny_pipe, prompt="resident", steps=16, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=91)
    _wait_steps(sched, base + 1)
    late = [sched.submit_request(
        tiny_pipe, prompt=f"burst {i}", steps=4 + i, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=92 + i) for i in range(3)]
    results = [fut.result(timeout=300) for fut in late]
    imgs = [pending.wait() for pending, _ in results]
    pending_a, info_a = fa.result(timeout=300)
    img_a = pending_a.wait()
    stats = sched.stats()
    sched.shutdown()

    assert stats.get("lane_resizes", 0) >= 1, stats  # the loop closed
    # the burst retired from a GROWN lane (>= 4 rows; the long resident
    # may legitimately see the lane shrink again before it retires)
    assert max(info["lane_width"] for _, info in results) >= 4, results
    solo_a, _ = tiny_pipe(GenerateRequest(
        prompt="resident", steps=16, guidance_scale=7.5, height=64,
        width=64, seed=91))
    _close(img_a, solo_a)
    for i, img in enumerate(imgs):
        solo, _ = tiny_pipe(GenerateRequest(
            prompt=f"burst {i}", steps=4 + i, guidance_scale=7.5,
            height=64, width=64, seed=92 + i))
        _close(img, solo)


def test_adaptive_resize_compiles_only_new_lattice_widths(
        tiny_pipe, monkeypatch):
    """Resizes stay on the compile-cache lattice: the first pass through
    a traffic pattern compiles its widths once; an identical second
    pass (fresh scheduler, same widths) compiles NOTHING — growth is a
    cache hit, and admission itself never compiles either way."""
    monkeypatch.delenv("CHIASWARM_STEPPER_LANE_WIDTH", raising=False)
    monkeypatch.setenv("CHIASWARM_STEPPER_MIN_WIDTH", "2")

    def one_pass():
        sched = StepScheduler()
        base = sched.stats().get("steps_executed", 0)
        first = sched.submit_request(
            tiny_pipe, prompt="lead", steps=8, guidance_scale=7.5,
            height=64, width=64, rows=1, seed=95)
        _wait_steps(sched, base + 1)
        rest = [sched.submit_request(
            tiny_pipe, prompt=f"tail {i}", steps=5, guidance_scale=7.5,
            height=64, width=64, rows=1, seed=96 + i) for i in range(3)]
        for fut in [first] + rest:
            fut.result(timeout=300)[0].wait()
        resizes = sched.stats().get("lane_resizes", 0)
        sched.shutdown()
        return resizes

    assert one_pass() >= 1  # warm pass: the growth widths compile here
    before = GLOBAL_CACHE.executables.stats["misses"]
    one_pass()
    after = GLOBAL_CACHE.executables.stats["misses"]
    assert after == before, (before, after)


# ---- ISSUE 27: a lone job rides a lane of its own bucket ----------------


@pytest.mark.parametrize("rows", [1, 2])
def test_lone_job_opens_its_own_bucket_and_pays_no_padding(
        tiny_pipe, monkeypatch, rows):
    """A fresh lane is as wide as its first job's bucket: a one-image
    job rides width 1 (a two-image job width 2), no row-step of the
    whole job is padding, the lane never resizes, and the images are
    the solo run's."""
    monkeypatch.delenv("CHIASWARM_STEPPER_LANE_WIDTH", raising=False)
    monkeypatch.delenv("CHIASWARM_STEPPER_MIN_WIDTH", raising=False)
    sched = StepScheduler()
    assert sched.initial_width(rows, 64, 64) == rows
    pending, info = sched.submit_request(
        tiny_pipe, prompt="alone", steps=9, guidance_scale=7.5,
        height=64, width=64, rows=rows, seed=271).result(timeout=300)
    imgs = pending.wait()
    stats = sched.stats()
    sched.shutdown()
    assert info["lane_width"] == rows
    assert stats.get("row_steps_padded", 0) == 0, stats
    assert stats["row_steps_active"] == 9 * rows
    assert stats.get("lane_resizes", 0) == 0
    solo, _ = tiny_pipe(GenerateRequest(
        prompt="alone", steps=9, guidance_scale=7.5, height=64, width=64,
        batch=rows, seed=271))
    _close(imgs, solo)


def test_lane_widens_1_to_2_for_a_second_job_and_returns_to_1(
        tiny_pipe, monkeypatch):
    """A second job arriving mid-flight grows the width-1 lane to 2 at
    the next boundary; once it has retired and the first has been alone
    for ``patience`` boundaries the lane is back at 1 — and across both
    rebuilds of the row file each job's image is its solo run's."""
    monkeypatch.delenv("CHIASWARM_STEPPER_LANE_WIDTH", raising=False)
    monkeypatch.delenv("CHIASWARM_STEPPER_MIN_WIDTH", raising=False)
    sched = StepScheduler()
    base = sched.stats().get("steps_executed", 0)
    fa = sched.submit_request(
        tiny_pipe, prompt="first", steps=16, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=272)
    _wait_steps(sched, base + 1)
    fb = sched.submit_request(
        tiny_pipe, prompt="second", steps=3, guidance_scale=5.0,
        height=64, width=64, rows=1, seed=273)
    pending_b, info_b = fb.result(timeout=300)
    pending_a, info_a = fa.result(timeout=300)
    img_a, img_b = pending_a.wait(), pending_b.wait()
    stats = sched.stats()
    sched.shutdown()
    assert info_a["lane"] == info_b["lane"]
    assert info_b["lane_width"] == 2 and info_a["lane_width"] == 1
    assert stats["lane_resizes"] == 2, stats   # 1 -> 2, then 2 -> 1
    # padding only while the first job was alone at width 2: the steps
    # between the ``patience`` boundaries the way back waits for
    from chiaswarm_tpu.serving.stepper import LaneWidthController

    patience = LaneWidthController().patience
    assert patience - 1 <= stats["row_steps_padded"] < 16 - 3, stats
    solo_a, _ = tiny_pipe(GenerateRequest(
        prompt="first", steps=16, guidance_scale=7.5, height=64,
        width=64, seed=272))
    solo_b, _ = tiny_pipe(GenerateRequest(
        prompt="second", steps=3, guidance_scale=5.0, height=64,
        width=64, seed=273))
    _close(img_a, solo_a)
    _close(img_b, solo_b)


def test_stale_poll_hint_does_not_widen_the_lane_it_announced(
        tiny_pipe, monkeypatch):
    """The poll that announces a job leaves a hint of one row; the
    job's own arrival burns it. A lane driver that read the hint before
    its idle wait must not count it beside that very job when the
    enqueue wakes it: the signals are read anew after every wake."""
    monkeypatch.delenv("CHIASWARM_STEPPER_LANE_WIDTH", raising=False)
    monkeypatch.delenv("CHIASWARM_STEPPER_MIN_WIDTH", raising=False)
    sched = StepScheduler()
    first = sched.submit_request(
        tiny_pipe, prompt="warm", steps=3, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=274)
    real_signal = sched.demand_signal
    announced = []

    def poll_lands_as_the_driver_reads(now=None):
        # the next job's poll returns exactly when the driver comes
        # back around after the first job resolved: the read that
        # precedes the idle wait sees the fresh hint
        if first.done() and not announced:
            announced.append(True)
            sched.note_poll(1)
        return real_signal(now)

    monkeypatch.setattr(sched, "demand_signal",
                        poll_lands_as_the_driver_reads)
    first.result(timeout=300)[0].wait()
    end = time.monotonic() + 30
    while not announced and time.monotonic() < end:
        time.sleep(0.005)
    assert announced
    time.sleep(0.1)     # the driver is in its idle wait by now
    _pending, info = sched.submit_request(
        tiny_pipe, prompt="announced", steps=3, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=275).result(timeout=300)
    stats = sched.stats()
    sched.shutdown()
    assert info["lane_width"] == 1
    assert stats.get("lane_resizes", 0) == 0, stats
    assert stats.get("row_steps_padded", 0) == 0, stats


class _MeshSlot:
    data_width = 2


@pytest.mark.parametrize("hint,arrived,age_s,shard_rows,want", [
    (0, 0, 0.0, False, 1),    # nothing announced: the job's own bucket
    (3, 1, 0.0, False, 4),    # a poll of 3, this job the first: 1 + 2
    (1, 1, 0.0, False, 1),    # the hint announced only this job
    (3, 1, 5.0, False, 1),    # a hint older than its 2 s says nothing
    (0, 0, 0.0, True, 2),     # row-sharded: the data axis divides it
])
def test_initial_width_holds_the_rows_in_evidence(
        monkeypatch, hint, arrived, age_s, shard_rows, want):
    monkeypatch.delenv("CHIASWARM_STEPPER_LANE_WIDTH", raising=False)
    monkeypatch.delenv("CHIASWARM_STEPPER_MIN_WIDTH", raising=False)
    if shard_rows:
        monkeypatch.setenv("CHIASWARM_STEPPER_SHARD_ROWS", "1")
    sched = StepScheduler(_MeshSlot())
    if hint:
        sched.note_poll(hint, now=time.monotonic() - age_s)
    if arrived:
        sched._note_arrival(arrived)
    assert sched.initial_width(1, 1024, 1024) == want


# ---- overload hooks (ISSUE 9): eviction retire + admission cap ---------


def test_eviction_retire_hook_frees_idle_lane_immediately(
        tiny_pipe, monkeypatch):
    """ISSUE 9 satellite: an idle lane asked to retire by the residency
    eviction hook frees its device state NOW — long before the idle
    grace (pinned to 10 minutes here so it provably wasn't the
    timeout), counted as lanes_evict_retired."""
    monkeypatch.setenv("CHIASWARM_STEPPER_IDLE_S", "600")
    from chiaswarm_tpu.serving.stepper import retire_lanes_for_owner

    sched = StepScheduler()
    fut = sched.submit_request(
        tiny_pipe, prompt="soon evicted", steps=3, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=41)
    fut.result(timeout=300)[0].wait()
    assert sched.stats()["lanes_live"] == 1  # idle but resident

    assert retire_lanes_for_owner(id(tiny_pipe.c)) >= 1
    end = time.monotonic() + 30
    while time.monotonic() < end and sched.stats()["lanes_live"]:
        time.sleep(0.02)
    stats = sched.stats()
    assert stats["lanes_live"] == 0, stats
    assert stats.get("lanes_evict_retired", 0) >= 1
    # rows were never harmed: nothing failed or expired
    assert stats.get("rows_failed", 0) == 0


def test_eviction_retire_waits_for_resident_rows(tiny_pipe, monkeypatch):
    """A BUSY lane asked to retire finishes its resident rows first
    (their params are still live on device), then retires at drain —
    the in-flight job completes normally."""
    monkeypatch.setenv("CHIASWARM_STEPPER_IDLE_S", "600")
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.05")
    from chiaswarm_tpu.serving.stepper import retire_lanes_for_owner

    sched = StepScheduler()
    base = sched.stats().get("steps_executed", 0)
    fut = sched.submit_request(
        tiny_pipe, prompt="evicted mid-flight", steps=10,
        guidance_scale=7.5, height=64, width=64, rows=1, seed=42)
    _wait_steps(sched, base + 2)
    assert retire_lanes_for_owner(id(tiny_pipe.c)) >= 1
    pending, _info = fut.result(timeout=300)
    assert pending.wait().shape[0] == 1      # the job completed
    end = time.monotonic() + 30
    while time.monotonic() < end and sched.stats()["lanes_live"]:
        time.sleep(0.02)
    stats = sched.stats()
    assert stats["lanes_live"] == 0, stats
    assert stats.get("rows_failed", 0) == 0
    assert stats.get("rows_completed", 0) >= 1


def test_admission_cap_throttles_rows_per_boundary(tiny_pipe, monkeypatch):
    """The brownout rung (node/overload.py via set_admission_cap): with
    cap=1, two jobs pending at the same boundary splice in one per
    boundary; the uncapped control admits both at once. The cap can
    never wedge a job wider than itself (first admit always allowed)."""
    monkeypatch.setenv("CHIASWARM_STEPPER_LANE_WIDTH", "4")
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.2")

    def run_pair(cap):
        sched = StepScheduler()
        if cap is not None:
            sched.set_admission_cap(cap)
            assert sched.admission_cap() == cap
        base = sched.stats().get("steps_executed", 0)
        lead = sched.submit_request(
            tiny_pipe, prompt="lead", steps=12, guidance_scale=7.5,
            height=64, width=64, rows=1, seed=51)
        _wait_steps(sched, base + 1)
        pair = [sched.submit_request(
            tiny_pipe, prompt=f"pending {i}", steps=3,
            guidance_scale=7.5, height=64, width=64, rows=1,
            seed=52 + i) for i in range(2)]
        infos = [fut.result(timeout=300)[1] for fut in pair]
        lead.result(timeout=300)[0].wait()
        sched.shutdown()
        return [info["admitted_at_step"] for info in infos]

    capped = run_pair(1)
    assert capped[0] != capped[1], capped      # one row per boundary
    uncapped = run_pair(None)
    assert uncapped[0] == uncapped[1], uncapped  # both splice together
