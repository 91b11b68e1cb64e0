"""swarmguard (ISSUE 10): gray-failure detection + the self-healing
ladder.

Four layers:

- **Units** (no jax): the watchdog monitor (arm/fire/disarm races),
  the DeviceGuard ladder (streaks, rung escalation order, recovery),
  hang-budget clamping, chaos-plan parsing, the output screens, and
  the failure-taxonomy membership of ``invalid_output``/``bad_asset``.
- **Lane-level** (real tiny lanes): a scripted wedge inside a step's
  armed window condemns the lane from the monitor thread; the rows'
  futures fail with LaneHung carrying the last step-boundary
  checkpoint, and resubmitting with it yields a BIT-IDENTICAL image to
  the uninterrupted run (the PR-6 resume-equivalence gate, reused). A
  scripted NaN injection retires exactly the poisoned row's job as
  ``invalid_output`` while its lane peer completes and matches solo.
- **Worker-level**: the executor heals a condemned lane transparently
  (the result carries ``stepper.resume_step >= 1``); the quarantine
  rung shrinks a 2-chip slot's mesh to the healthy chip (capacity
  re-advertised); the restart rung requests a graceful stop with the
  distinct supervisor exit code.
- **THE acceptance gate**: a 3-worker MiniHive fleet under mixed
  workloads with one scripted mid-lane wedge and one injected NaN row
  — every job settles exactly once (completed / redispatched
  ``invalid_output`` / resumed), the condemned lane's surviving rows
  resume at step >= 1, no garbage image uploads, and the health score
  + heal-rung transitions are visible on /metrics.

Everything is hermetic, scripted/seeded, on the CPU test mesh.
"""

from __future__ import annotations

import asyncio
import base64
import io
import threading
import time

import numpy as np
import pytest

from chiaswarm_tpu.node.resilience import (
    BREAKER_KINDS,
    NONFATAL_KINDS,
    REDISPATCH_KINDS,
    RETRYABLE_KINDS,
    BadAssetError,
    classify_exception,
    classify_result,
)
from chiaswarm_tpu.obs.metrics import Registry
from chiaswarm_tpu.serving import guard
from chiaswarm_tpu.serving.guard import (
    GUARD_RESTART_EXIT_CODE,
    DeviceGuard,
    InvalidOutput,
    LaneChaos,
    LaneHung,
    StepHung,
    Watchdog,
    hang_budget_s,
    screen_images,
    solo_hang_budget_s,
)


@pytest.fixture(autouse=True)
def _tmp_root(tmp_path, monkeypatch):
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path))
    return tmp_path


@pytest.fixture(autouse=True)
def _fresh_chaos(monkeypatch):
    """Each test re-arms the one-shot chaos seams and starts with the
    chaos env unset (tests opt in explicitly)."""
    for name in (guard.ENV_CHAOS_WEDGE, guard.ENV_CHAOS_SLOW,
                 guard.ENV_CHAOS_NAN, guard.ENV_ENABLE,
                 guard.ENV_HANG_FACTOR, guard.ENV_HANG_FLOOR,
                 guard.ENV_HANG_CEIL):
        monkeypatch.delenv(name, raising=False)
    guard.reset_chaos()
    yield
    guard.reset_chaos()


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------


def test_watchdog_fires_then_disarm_reports_it():
    dog = Watchdog()
    fired = []
    ticket = dog.arm(0.05, lambda: fired.append(1), tag="t1")
    deadline = time.monotonic() + 5
    while not fired and time.monotonic() < deadline:
        time.sleep(0.01)
    assert fired == [1]
    assert dog.disarm(ticket) is True


def test_watchdog_disarm_before_deadline_never_fires():
    dog = Watchdog()
    fired = []
    ticket = dog.arm(5.0, lambda: fired.append(1), tag="t2")
    assert dog.disarm(ticket) is False
    time.sleep(0.05)
    assert not fired
    # disarming twice (or an unknown ticket) is harmless
    assert dog.disarm(ticket) is False


def test_hang_budget_clamps_and_cold_uses_ceiling(monkeypatch):
    monkeypatch.setenv(guard.ENV_HANG_FACTOR, "10")
    monkeypatch.setenv(guard.ENV_HANG_FLOOR, "2")
    monkeypatch.setenv(guard.ENV_HANG_CEIL, "50")
    assert hang_budget_s(0.0) == 50.0          # cold: first call compiles
    assert hang_budget_s(0.01) == 2.0          # floor
    assert hang_budget_s(1.0) == 10.0          # factor x ewma
    assert hang_budget_s(100.0) == 50.0        # ceiling
    # solo: never armed cold (no EWMA evidence / no steps)
    assert solo_hang_budget_s(0.0, 30) is None
    assert solo_hang_budget_s(0.5, 0) is None
    assert solo_hang_budget_s(0.5, 10) == 50.0  # clamped to ceiling


def test_device_guard_ladder_escalates_in_order_and_recovers():
    dg = DeviceGuard(cache_flush_after=3, quarantine_after=5,
                     restart_after=7, metrics_registry=Registry())
    dg.seed_devices(["3"])
    assert dg.health_scores() == {"3": 1.0}
    dg.note_hang(["3"])                    # streak 2 (hang weighs 2)
    assert dg.take_actions() == []
    dg.note_invalid_output(["3"], model="m")   # streak 3 -> cache_flush
    assert [a.rung for a in dg.take_actions()] == ["cache_flush"]
    dg.note_hang(["3"])                    # streak 5 -> quarantine
    actions = dg.take_actions()
    assert [a.rung for a in actions] == ["device_quarantine"]
    assert dg.quarantined == {"3"}
    dg.note_hang(["3"])                    # streak 7 -> restart
    assert [a.rung for a in dg.take_actions()] == ["restart"]
    assert dg.restart_requested is True
    assert dg.health_scores()["3"] == 0.0
    # each rung fires ONCE per sickness episode
    dg.note_hang(["3"])
    assert dg.take_actions() == []
    # recovery: OK events decay the streak; at zero the ladder re-arms
    for _ in range(20):
        dg.note_ok(["3"])
    assert dg.health_scores()["3"] == 1.0
    for _ in range(2):
        dg.note_hang(["3"])
    assert [a.rung for a in dg.take_actions()] == ["cache_flush"]


def test_device_guard_disabled_counts_but_never_acts():
    dg = DeviceGuard(enabled=False, cache_flush_after=1,
                     quarantine_after=2, restart_after=3,
                     metrics_registry=Registry())
    for _ in range(5):
        dg.note_hang(["0"])
    assert dg.take_actions() == []
    assert dg.snapshot()["hangs"] == 5


def test_chaos_plan_parses_and_one_shots(monkeypatch):
    monkeypatch.setenv(guard.ENV_CHAOS_WEDGE, "3:2.5")
    monkeypatch.setenv(guard.ENV_CHAOS_NAN, "4:1")
    monkeypatch.setenv(guard.ENV_CHAOS_SLOW, "3.0")
    plan = LaneChaos.from_env()
    assert plan.wedge_at(2) == 0.0
    assert plan.wedge_at(3) == 2.5
    assert plan.wedge_at(3) == 0.0          # one shot, process-wide
    # the NaN seam WANTS to fire at-or-after its step; the lane
    # consumes the one-shot only once the row is eligible
    assert plan.nan_wants(3) is None
    assert plan.nan_wants(4) == 1
    assert plan.nan_wants(5) == 1           # still pending
    assert guard.consume_chaos("nan") is True
    assert guard.consume_chaos("nan") is False
    assert plan.slow_extra_s(0.1) == pytest.approx(0.2)
    # malformed env values never raise — chaos defaults off
    monkeypatch.setenv(guard.ENV_CHAOS_WEDGE, "garbage")
    assert LaneChaos.from_env().wedge_step is None


def test_screen_images_catches_poison_and_passes_real_frames():
    rng = np.random.default_rng(7)
    screen_images(rng.integers(0, 255, (2, 8, 8, 3)).astype(np.uint8))
    with pytest.raises(InvalidOutput):
        screen_images(np.zeros((1, 8, 8, 3), np.uint8))   # black frame
    with pytest.raises(InvalidOutput):
        screen_images(np.full((1, 8, 8, 3), np.nan, np.float32))
    ok_and_black = np.concatenate(
        [rng.integers(1, 255, (1, 8, 8, 3)).astype(np.uint8),
         np.zeros((1, 8, 8, 3), np.uint8)])
    with pytest.raises(InvalidOutput):
        screen_images(ok_and_black)


def test_screen_images_disabled_by_env(monkeypatch):
    monkeypatch.setenv(guard.ENV_ENABLE, "0")
    screen_images(np.zeros((1, 8, 8, 3), np.uint8))  # no raise


def test_failure_taxonomy_membership():
    # invalid_output: redispatchable AND breaker fodder (a checkpoint
    # that keeps producing NaN is broken; a device that does is sick)
    assert "invalid_output" in REDISPATCH_KINDS
    assert "invalid_output" in BREAKER_KINDS
    assert "invalid_output" in NONFATAL_KINDS
    # bad_asset: non-fatal, but neither retried locally nor breaker
    # fodder nor hive-redispatched by kind
    assert "bad_asset" in NONFATAL_KINDS
    assert "bad_asset" not in RETRYABLE_KINDS
    assert "bad_asset" not in BREAKER_KINDS
    assert "bad_asset" not in REDISPATCH_KINDS
    assert classify_exception(InvalidOutput("x")) == "invalid_output"
    assert classify_exception(StepHung("x")) == "transient"
    assert classify_exception(BadAssetError("x")) == "bad_asset"
    # BadAssetError still satisfies legacy ValueError handling
    assert isinstance(BadAssetError("x"), ValueError)

    from chiaswarm_tpu.node.executor import error_result

    envelope = error_result({"id": "g1", "content_type":
                             "application/json"}, InvalidOutput("nan"),
                            kind="invalid_output")
    assert "fatal_error" not in envelope
    assert classify_result(envelope) == "invalid_output"


# ---------------------------------------------------------------------------
# lane-level: wedge -> condemn -> resume, NaN -> invalid_output
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_pipe():
    from chiaswarm_tpu.pipelines import Components, DiffusionPipeline

    return DiffusionPipeline(Components.random("tiny", seed=0))


def _wait_steps(sched, n, timeout=120.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if sched.stats().get("steps_executed", 0) >= n:
            return
        time.sleep(0.005)
    raise AssertionError(f"never reached {n} steps: {sched.stats()}")


def test_wedge_condemn_resume_bit_identical(tiny_pipe, monkeypatch):
    """THE lane-rebuild gate: a wedged step condemns the lane, the
    job's future fails with LaneHung + the last step-boundary
    checkpoint, and re-admission to a fresh lane resumes at step k —
    producing the BIT-IDENTICAL image of an uninterrupted lane run
    (the PR-6 resume-equivalence bar)."""
    from chiaswarm_tpu.serving.stepper import StepScheduler

    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")

    # uninterrupted reference (also warms the lane executables so the
    # wedged run's budget comes from a real step EWMA, not a compile)
    ref_sched = StepScheduler()
    ref_fut = ref_sched.submit_request(
        tiny_pipe, prompt="wedge me", steps=8, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=404)
    ref_pending, _ = ref_fut.result(timeout=300)
    ref_img = ref_pending.wait()
    ref_sched.shutdown()

    # wedged run: lane-local step 3 sleeps 3s with a sub-second budget
    monkeypatch.setenv(guard.ENV_HANG_FACTOR, "3")
    monkeypatch.setenv(guard.ENV_HANG_FLOOR, "0.2")
    monkeypatch.setenv(guard.ENV_CHAOS_WEDGE, "3:3.0")
    guard.reset_chaos()
    sched = StepScheduler()
    # feed the scheduler's step EWMA so the wedge's budget is tight
    # (a fresh scheduler would arm the first steps at the ceiling)
    sched.note_step_seconds(0.05)
    fut = sched.submit_request(
        tiny_pipe, prompt="wedge me", steps=8, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=404)
    with pytest.raises(LaneHung) as excinfo:
        fut.result(timeout=300)
    resume = excinfo.value.resume
    assert isinstance(resume, dict) and resume.get("kind") == "lane"
    assert 1 <= int(resume["step"]) < 8
    stats = sched.stats()
    assert stats.get("lanes_condemned") == 1
    assert stats.get("rows_hung", 0) >= 1

    # re-admission: fresh lane, resumed at the checkpointed step
    monkeypatch.delenv(guard.ENV_CHAOS_WEDGE)
    healed = sched.submit_request(
        tiny_pipe, prompt="wedge me", steps=8, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=404, resume=resume)
    pending, info = healed.result(timeout=300)
    img = pending.wait()
    assert info["resume_step"] == int(resume["step"])
    assert np.array_equal(img, ref_img)     # bit-identical
    sched.shutdown()


def test_nan_row_retires_alone_while_lane_peer_completes(
        tiny_pipe, monkeypatch):
    """A NaN-poisoned row retires with InvalidOutput at the next
    checkpoint boundary; the job sharing its lane keeps stepping and
    matches the solo run — the poison never takes peers down and never
    decodes."""
    from chiaswarm_tpu.pipelines import GenerateRequest
    from chiaswarm_tpu.serving.stepper import StepScheduler

    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    monkeypatch.setenv(guard.ENV_CHAOS_NAN, "2:0")
    guard.reset_chaos()
    sched = StepScheduler()
    doomed = sched.submit_request(
        tiny_pipe, prompt="poisoned", steps=8, guidance_scale=7.5,
        height=64, width=64, rows=1, seed=71)
    _wait_steps(sched, 1)
    survivor = sched.submit_request(
        tiny_pipe, prompt="survivor", steps=5, guidance_scale=6.0,
        height=64, width=64, rows=1, seed=72)
    with pytest.raises(InvalidOutput):
        doomed.result(timeout=300)
    pending, info = survivor.result(timeout=300)
    img = pending.wait()
    assert info["lane"] is not None
    stats = sched.stats()
    assert stats.get("rows_invalid") == 1
    assert stats.get("lanes_condemned", 0) == 0

    solo, _ = tiny_pipe(GenerateRequest(
        prompt="survivor", steps=5, guidance_scale=6.0, height=64,
        width=64, seed=72))
    diff = np.abs(img.astype(int) - solo.astype(int))
    assert diff.max() <= 3 and (diff <= 1).mean() > 0.99
    sched.shutdown()


@pytest.mark.slow
def test_executor_heals_condemned_lane_transparently(
        tiny_pipe, monkeypatch):
    """Worker-facing contract: a wedge mid-lane is invisible to the
    caller — synchronous_do_work returns a SUCCESS whose config stamps
    the resume step, and the slot's DeviceGuard heard the hang. (Slow
    tier: the same executor heal path runs inside the tier-1 fleet
    acceptance gate; this is the isolated, single-worker variant.)"""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec
    from chiaswarm_tpu.node.executor import synchronous_do_work
    from chiaswarm_tpu.node.registry import ModelRegistry

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])
    slot = pool.slots[0]
    slot._guard = DeviceGuard(metrics_registry=Registry())

    def job(i):
        return {"id": f"heal-{i}", "model_name": "tiny",
                "prompt": f"heal prompt {i}", "seed": 500 + i,
                "num_inference_steps": 8, "guidance_scale": 7.5,
                "height": 64, "width": 64, "content_type": "image/png"}

    # warm run: executables compiled, step EWMA fed
    warm = synchronous_do_work(job(0), slot, registry)
    assert warm["pipeline_config"].get("error") is None
    stepper = slot._stepper
    assert stepper.step_ewma() > 0.0
    # retire the warm lane so the wedged job opens a FRESH one whose
    # lane-local step counter starts at 1 (the chaos trigger is
    # lane-local); the executables stay cached, so step 1 of the new
    # lane dispatches without compiling and the tight budget is safe
    stepper.shutdown()

    monkeypatch.setenv(guard.ENV_HANG_FACTOR, "3")
    monkeypatch.setenv(guard.ENV_HANG_FLOOR, "0.2")
    monkeypatch.setenv(guard.ENV_CHAOS_WEDGE, "3:3.0")
    guard.reset_chaos()

    result = synchronous_do_work(job(1), slot, registry)
    config = result["pipeline_config"]
    assert config.get("error") is None, config
    info = config.get("stepper") or {}
    stats = stepper.stats()
    assert stats.get("lanes_condemned", 0) == 1, stats
    assert int(info.get("resume_step", 0)) >= 1, info
    assert slot._guard.snapshot()["hangs"] >= 1
    assert slot._guard.snapshot()["condemned_lanes"] >= 1
    stepper.shutdown()


# ---------------------------------------------------------------------------
# worker-level rungs: quarantine shrinks capacity, restart exit code
# ---------------------------------------------------------------------------


def _guard_worker(pool, **settings_over):
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    base = dict(hive_uri="http://hive", hive_token="t",
                worker_name="guard-w", install_signal_handlers=False)
    base.update(settings_over)
    return Worker(settings=Settings(**base), pool=pool,
                  registry=ModelRegistry(catalog=[], allow_random=True))


def test_quarantine_rung_shrinks_capacity_and_restart_rung_exits():
    """The two heavy rungs, end to end through the worker: escalating
    hangs on one chip of a 2-chip slot quarantine it — the slot mesh
    shrinks to the healthy chip and /healthz re-advertises the
    capacity — and further sickness requests the graceful restart with
    the distinct supervisor exit code."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 2}),
                    devices=jax.devices()[:2])
    worker = _guard_worker(pool, guard_cache_flush_after=2,
                           guard_quarantine_after=4,
                           guard_restart_after=6)
    slot = worker.pool.slots[0]
    assert slot.data_width == 2
    assert worker.health()["chips_in_service"] == 2
    sick = str(slot.mesh.devices.flatten()[0].id)

    worker.guard.note_hang([sick])                  # streak 2: flush
    worker.guard.note_hang([sick])                  # streak 4: quarantine
    worker._apply_heal_rungs()
    assert slot.data_width == 1
    assert sick not in {str(d.id) for d in slot.mesh.devices.flatten()}
    health = worker.health()
    assert health["chips_in_service"] == 1
    assert health["guard"]["quarantined"] == [sick]

    worker.guard.note_hang([sick])                  # streak 6: restart
    worker._apply_heal_rungs()
    assert worker._stop.is_set()
    assert worker.exit_code == GUARD_RESTART_EXIT_CODE
    # the /metrics mirror shows the rung transitions + health score
    body = worker.metrics.render()
    assert 'chiaswarm_guard_heal_rung_total{rung="device_quarantine"} 1' \
        in body
    assert 'chiaswarm_guard_heal_rung_total{rung="restart"} 1' in body
    assert f'chiaswarm_guard_device_health{{device="{sick}"}} 0' in body
    assert "chiaswarm_guard_quarantined_devices 1" in body


def test_single_chip_slot_declines_quarantine_and_escalates():
    """A 1-chip slot cannot shrink: the quarantine rung no-ops loudly
    and the next rung (restart) still fires — a sick only-chip heals by
    replacement, not amputation."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])
    worker = _guard_worker(pool, guard_quarantine_after=2,
                           guard_restart_after=4)
    slot = worker.pool.slots[0]
    sick = str(slot.mesh.devices.flatten()[0].id)
    worker.guard.note_hang([sick])
    worker._apply_heal_rungs()
    assert slot.data_width == 1                     # unchanged
    worker.guard.note_hang([sick])
    worker._apply_heal_rungs()
    assert worker.exit_code == GUARD_RESTART_EXIT_CODE


def test_solo_watchdog_raises_stephung_and_notes_health(monkeypatch):
    """The solo denoise watchdog: the FIRST watched call on a slot runs
    under the generous ceiling (the solo program may be compiling —
    the code-review finding); later calls that outlive the tight
    steps-x-EWMA budget raise StepHung on return (classified transient
    -> the ladder re-runs them) and the device guard hears a solo-phase
    hang."""
    from chiaswarm_tpu.serving.guard import watch_solo

    class FakeStepper:
        @staticmethod
        def step_ewma():
            return 0.01

    class Slot:
        _stepper = FakeStepper()

    slot = Slot()
    slot._guard = DeviceGuard(metrics_registry=Registry())
    monkeypatch.setenv(guard.ENV_HANG_FACTOR, "1")
    monkeypatch.setenv(guard.ENV_HANG_FLOOR, "0.05")
    # first watched call of a program variant: ceiling budget — a slow
    # (compiling) call is NOT flagged, and the variant key is marked
    # warm for this cache-flush epoch afterwards
    with watch_solo(slot, steps=5, key=("m", 64, 64)):
        time.sleep(0.3)
    assert slot._guard.snapshot()["hangs"] == 0
    epoch, warm = getattr(slot, "_guard_solo_warm")
    assert epoch == guard.flush_epoch() and ("m", 64, 64) in warm
    # second call of the SAME variant: the tight budget applies
    with pytest.raises(StepHung):
        with watch_solo(slot, steps=5, key=("m", 64, 64)):
            time.sleep(0.5)
    snap = slot._guard.snapshot()
    assert snap["hangs"] == 1
    # a DIFFERENT variant (new model/shape = its own compile-cache
    # entry) re-colds to the ceiling — no flag on its slow first call
    with watch_solo(slot, steps=5, key=("other", 64, 64)):
        time.sleep(0.3)
    assert slot._guard.snapshot()["hangs"] == 1
    # a fast call of a warm variant is never flagged
    with watch_solo(slot, steps=5, key=("m", 64, 64)):
        pass
    assert slot._guard.snapshot()["hangs"] == 1
    # cold (no EWMA): never armed
    slot._stepper = type("S", (), {"step_ewma": staticmethod(
        lambda: 0.0)})()
    with watch_solo(slot, steps=5):
        time.sleep(0.1)


def test_screen_images_accepts_single_image_with_uniform_rows():
    """Regression (code review): an (H, W, C) array is ONE image, not a
    stack of H row-frames — a legitimate solid border/sky row must not
    read as a constant frame."""
    rng = np.random.default_rng(11)
    img = rng.integers(0, 255, (64, 64, 3)).astype(np.uint8)
    img[0, :, :] = 255          # solid top border row
    screen_images(img)          # no raise
    with pytest.raises(InvalidOutput):
        screen_images(np.full((64, 64, 3), 7, np.uint8))  # truly flat


def test_quarantine_amputates_at_most_one_chip_per_process():
    """Regression (code review): events are slot-granular, so every
    chip of a slot crosses the quarantine threshold together — the
    ladder must amputate ONE chip, not collapse the mesh chip by chip;
    continued sickness escalates to restart instead."""
    dg = DeviceGuard(cache_flush_after=2, quarantine_after=4,
                     restart_after=6, metrics_registry=Registry())
    devices = ["0", "1", "2", "3"]
    dg.note_hang(devices)                  # streak 2 -> one cache_flush
    assert [a.rung for a in dg.take_actions()] == ["cache_flush"]
    dg.note_hang(devices)                  # streak 4 -> ONE quarantine
    actions = dg.take_actions()
    assert [a.rung for a in actions] == ["device_quarantine"]
    assert len(dg.quarantined) == 1
    dg.note_hang(devices)                  # streak 6 -> restart (once)
    assert [a.rung for a in dg.take_actions()] == ["restart"]
    assert len(dg.quarantined) == 1        # still one amputation


# ---------------------------------------------------------------------------
# THE acceptance gate: 3-worker fleet, scripted wedge + NaN row
# ---------------------------------------------------------------------------


def _png_array(result) -> np.ndarray:
    from PIL import Image

    blob = result["artifacts"]["primary"]["blob"]
    raw = base64.b64decode(blob) if isinstance(blob, str) else blob
    return np.asarray(Image.open(io.BytesIO(raw)).convert("RGB"))


def test_fleet_gate_wedge_and_nan_settle_exactly_once(monkeypatch):
    """ISSUE 10 acceptance: 3 real-lane workers on one MiniHive, mixed
    workloads, one scripted mid-lane wedge (condemn -> resume) and one
    injected NaN row (invalid_output -> hive redispatch). Every job
    settles exactly once, the condemned lane's surviving rows resume at
    step >= 1, no uploaded image is poisoned, and the guard's health +
    rung families are live on /metrics."""
    import aiohttp
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.05")
    # pinned width: every lane program compiles in the warm-up phase,
    # so no phase-2 dispatch ever pays a (budget-blowing) resize
    # compile under the tight watchdog
    monkeypatch.setenv("CHIASWARM_STEPPER_LANE_WIDTH", "2")
    # factor 25 over the ~0.1-0.2 s honest post-warm-up step keeps
    # honest steps far under the budget, while the wedge (below: twice
    # the widest budget a scheduler holds when it is armed) sails
    # over it however far GIL contention inflated the EWMA;
    # the ceiling stays at its (generous) default so any cold compile
    # — e.g. on a worker the warm-up poll race starved — never condemns
    monkeypatch.setenv(guard.ENV_HANG_FACTOR, "25")
    monkeypatch.setenv(guard.ENV_HANG_FLOOR, "1.0")
    guard.reset_chaos()

    registry_catalog = [{"name": "tiny", "family": "tiny",
                         "parameters": {}}]

    def job(tag, i, workflow="txt2img", **over):
        payload = {"id": f"{tag}-{i}", "model_name": "tiny",
                   "workflow": workflow,
                   "prompt": f"{tag} prompt {i}", "seed": 700 + i,
                   "num_inference_steps": 8, "guidance_scale": 7.5,
                   "height": 64, "width": 64,
                   "content_type": "image/png"}
        payload.update(over)
        return payload

    async def scenario():
        hive = MiniHive(lease_s=120.0, delay_s=0.01, max_jobs_per_poll=1)
        uri = await hive.start()
        workers = []
        for tag in ("a", "b", "c"):
            pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                            devices=jax.devices()[:1])
            workers.append(Worker(
                settings=Settings(
                    hive_uri=uri, hive_token="t",
                    worker_name=f"guardfleet-{tag}",
                    job_deadline_s=600.0, heartbeat_s=0.05,
                    poll_busy_s=0.02, poll_idle_s=0.05,
                    poll_backoff_base_s=0.02, poll_backoff_cap_s=0.1,
                    upload_retries=5, upload_retry_delay_s=0.02,
                    drain_timeout_s=30.0, result_drain_timeout_s=10.0,
                    install_signal_handlers=False,
                    health_bind_ephemeral=True),
                registry=ModelRegistry(catalog=registry_catalog,
                                       allow_random=True),
                pool=pool))
        tasks = [asyncio.create_task(w.run()) for w in workers]
        bodies = []
        try:
            # PHASE 1 (warm-up, chaos unarmed, generous cold budgets):
            # the same job SHAPES the gate jobs use (steps 4 lands in
            # the same capacity bucket as 12) — every lane executable
            # compiles here, and each scheduler's step EWMA becomes an
            # honest post-compile number
            hive.submit(job("warm", 0, num_inference_steps=4))
            hive.submit(job("warm", 1, num_inference_steps=4))
            hive.submit(job("warm", 2, workflow="img2img",
                            num_inference_steps=4,
                            start_image_uri=f"{uri}/assets/image.png",
                            strength=0.8))
            await hive.wait_for_results(3, timeout=600)

            # PHASE 2: arm the wedge (fired 5 post-arm steps in — its
            # job has checkpoints by then) and the NaN poison (row 0, 2
            # post-arm steps in), then release the gate jobs: mixed
            # workloads, two txt2img + one img2img. The wedge has to
            # outlast the budget the watchdog ARMS: factor x the step
            # EWMA its scheduler holds, which three workers compiling
            # under one GIL leave at 0.7-1.1 s on an idle host (budgets
            # of 17-27 s) and higher on a busy one. A wedge under the
            # budget never condemns — the old load-dependent failure
            # of 15 s x a sleep-overshoot factor — so size it from what
            # the watchdog will compare against. (A worker the warm-up
            # starved holds no EWMA yet. The EWMA only falls over the
            # five honest steps before the wedge fires, so twice the
            # widest budget is room enough.)
            ewmas = [slot._stepper.step_ewma() for worker in workers
                     for slot in worker.pool
                     if getattr(slot, "_stepper", None) is not None]
            wedge_s = max(15.0, 2.0 * max(
                guard.hang_budget_s(ewma) for ewma in ewmas if ewma > 0))
            monkeypatch.setenv(guard.ENV_CHAOS_WEDGE,
                               f"5:{wedge_s:.2f}")
            monkeypatch.setenv(guard.ENV_CHAOS_NAN, "2:0")
            guard.reset_chaos()
            hive.submit(job("gate", 0))
            hive.submit(job("gate", 1))
            hive.submit(job("gate", 2, workflow="img2img",
                            start_image_uri=f"{uri}/assets/image.png",
                            strength=0.8))
            await hive.wait_for_results(6, timeout=600)
            async with aiohttp.ClientSession() as session:
                for worker in workers:
                    for _ in range(100):
                        if getattr(worker, "health_address", None):
                            break
                        await asyncio.sleep(0.05)
                    host, port = worker.health_address
                    async with session.get(
                            f"http://{host}:{port}/metrics") as resp:
                        bodies.append(await resp.text())
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=60)
                                   for t in tasks),
                                 return_exceptions=True)
            for worker in workers:
                for slot in worker.pool:
                    stepper = getattr(slot, "_stepper", None)
                    if stepper is not None:
                        stepper.shutdown()
            await hive.stop()
        return hive, workers, bodies

    hive, workers, bodies = asyncio.run(scenario())
    # the condemned lane's driver is still asleep in its wedge; see it
    # out, or the next file of this xdist worker meets a live
    # ``stepper-lane-*`` thread (tests/test_chip_smoke.py counts them)
    for thread in threading.enumerate():
        if thread.name.startswith("stepper-lane-"):
            thread.join(timeout=120.0)
            assert not thread.is_alive(), thread.name

    # exactly-once settlement: completed / redispatched invalid_output
    uploaded = hive.uploaded_ids()
    assert sorted(uploaded) == ["gate-0", "gate-1", "gate-2",
                                "warm-0", "warm-1", "warm-2"]
    assert len(uploaded) == len(set(uploaded))
    assert hive.abandoned == []
    for result in hive.results:
        assert result["pipeline_config"].get("error") is None, result
        # no garbage image ever uploads: decode and screen every frame
        screen_images(_png_array(result), context="gate upload")

    # the NaN row traveled the redispatch path (invalid_output kind)
    redispatched = hive.metrics.get(
        "chiaswarm_hive_jobs_redispatched_total")
    assert redispatched.value(kind="invalid_output") >= 1

    # the condemned lane's rows resumed at step >= 1 somewhere
    resumed = [r for r in hive.results
               if int((r["pipeline_config"].get("stepper") or {})
                      .get("resume_step", 0)) >= 1]
    all_stats = [slot._stepper.stats()
                 for w in workers for slot in w.pool
                 if getattr(slot, "_stepper", None) is not None]
    assert sum(s.get("lanes_condemned", 0) for s in all_stats) >= 1
    assert resumed, [r["pipeline_config"].get("stepper")
                     for r in hive.results]
    assert sum(s.get("rows_invalid", 0) for s in all_stats) >= 1

    # the sick worker's health + rung transitions are on /metrics:
    # counters agree with the guard snapshots, and the families render
    snaps = [w.guard.snapshot() for w in workers]
    assert sum(s["hangs"] for s in snaps) >= 1
    assert sum(s["condemned_lanes"] for s in snaps) >= 1
    assert sum(s["invalid_outputs"] for s in snaps) >= 1
    merged = "\n".join(bodies)
    assert 'chiaswarm_guard_hangs_total{phase="lane"}' in merged
    assert "chiaswarm_guard_condemned_lanes_total" in merged
    assert 'chiaswarm_guard_heal_rung_total{rung="lane_rebuild"}' in merged
    assert 'chiaswarm_guard_invalid_outputs_total{model="tiny"}' in merged
    assert "chiaswarm_guard_device_health" in merged


# ---------------------------------------------------------------------------
# nightly seeded wedge/NaN soak (CI satellite; replay with
#   CHIASWARM_SOAK_SEED=<run id> pytest tests/test_guard.py --slow -k soak)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_guard_soak_seeded_wedge_nan(monkeypatch):
    """Seeded guard soak: a stream of lane jobs through one scheduler
    with a seeded wedge AND a seeded NaN injection — every job ends as
    exactly one of completed / LaneHung-healed / InvalidOutput, nothing
    hangs the suite, and the scheduler's books balance."""
    import os as _os

    from chiaswarm_tpu.pipelines import Components, DiffusionPipeline
    from chiaswarm_tpu.serving.stepper import StepScheduler

    seed = _os.environ.get("CHIASWARM_SOAK_SEED", "guard-soak")
    jobs = max(6, int(_os.environ.get("CHIASWARM_SOAK_JOBS", "120")) // 10)
    rng = np.random.default_rng(abs(hash(seed)) % (2 ** 32))
    wedge_step = int(rng.integers(2, 6))
    nan_step = int(rng.integers(2, 6))
    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    # pinned width: no adaptive-resize compiles can land under the
    # tight post-warm-up budget (a compile is not a gray failure)
    monkeypatch.setenv("CHIASWARM_STEPPER_LANE_WIDTH", "4")

    pipe = DiffusionPipeline(Components.random("tiny", seed=0))
    sched = StepScheduler()
    # warm-up under the default (generous) budget: the width-4 lane
    # executables compile here, and the step EWMA becomes honest
    warm = sched.submit_request(pipe, prompt="soak warm", steps=4,
                                guidance_scale=7.5, height=64, width=64,
                                rows=1, seed=999)
    warm.result(timeout=600)[0].wait()

    monkeypatch.setenv(guard.ENV_HANG_FACTOR, "20")
    monkeypatch.setenv(guard.ENV_HANG_FLOOR, "0.5")
    monkeypatch.setenv(guard.ENV_CHAOS_WEDGE, f"{wedge_step}:3.0")
    monkeypatch.setenv(guard.ENV_CHAOS_NAN, f"{nan_step}:0")
    guard.reset_chaos()
    args = {}
    futures = []
    for i in range(jobs):
        args[i] = dict(prompt=f"soak {i}",
                       steps=int(rng.integers(3, 9)),
                       guidance_scale=7.5, height=64, width=64, rows=1,
                       seed=1000 + i)
        futures.append((i, sched.submit_request(pipe, **args[i])))
        time.sleep(0.01)
    outcomes = {"ok": 0, "healed": 0, "invalid": 0, "lost": 0}

    def settle(i, fut, heal_budget=2):
        # the executor's heal policy, inlined: one re-admission (with
        # the condemnation checkpoint when one exists) per LaneHung
        try:
            pending, _info = fut.result(timeout=600)
            pending.wait()
            return "ok"
        except InvalidOutput:
            return "invalid"
        except LaneHung as exc:
            if heal_budget <= 0:
                return "lost"
            retry = sched.submit_request(
                pipe, resume=(exc.resume if isinstance(exc.resume, dict)
                              else None), **args[i])
            verdict = settle(i, retry, heal_budget - 1)
            return "healed" if verdict == "ok" else verdict

    for i, fut in futures:
        outcomes[settle(i, fut)] += 1
    assert sum(outcomes.values()) == jobs, outcomes
    assert outcomes["lost"] == 0, outcomes
    # every job settled as a real outcome; with all jobs co-resident
    # in one lane, the wedge can convert the whole population to
    # "healed" — completion is the invariant, not the plain-ok path
    assert outcomes["ok"] + outcomes["healed"] >= jobs - 1, outcomes
    assert outcomes["healed"] >= 1, outcomes
    stats = sched.stats()
    assert stats.get("lanes_condemned", 0) >= 1  # the wedge fired
    assert stats.get("rows_invalid", 0) == 1     # one-shot NaN
    sched.shutdown()
