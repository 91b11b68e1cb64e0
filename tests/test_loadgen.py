"""swarmload (ISSUE 9, node/loadgen.py): the load harness units, the
tuning-sweep pins, and THE acceptance gate.

Layers:

- **Model units**: seeded determinism of users/curves/schedules, the
  workload mix, percentile/reconcile helpers, and the controller
  simulators the sweeps are built on.
- **Sweep pins**: the shipped LaneWidthController gains and the
  residency prefetch-ranking window must equal the default-seed sweep
  winners — a default and the harness can never silently disagree.
- **Load smoke** (the fast CI leg): a small seeded diurnal run over
  overload-controlled workers settles every job exactly once.
- **THE ISSUE-9 acceptance gate**: scripted 10x offered load, mixed
  workloads, one mid-run worker kill — zero job loss (every job
  completed, shed-redispatched, or abandoned-by-policy), sheds and
  backpressure observed, p99 of admitted jobs within deadline, and the
  capacity model populated.
- **Nightly soak** (slow tier): a bigger diurnal fleet soak seeded from
  the run id (chaos-soak.yml).
"""

from __future__ import annotations

import asyncio
import os
import time

import pytest

from chiaswarm_tpu.node import loadgen
from chiaswarm_tpu.node.loadgen import (
    DEFAULT_PROFILES,
    DiurnalCurve,
    KillPlan,
    LoadHive,
    RosterPlan,
    SyntheticExecutor,
    UserPopulation,
    build_scenario,
    generate_schedule,
    percentile,
    reconcile,
    run_load,
)
from chiaswarm_tpu.node.resilience import classify_result


@pytest.fixture(autouse=True)
def _tmp_root(tmp_path, monkeypatch):
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path))
    return tmp_path


# ---------------------------------------------------------------------------
# model units
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    assert percentile([], 0.99) == 0.0
    assert percentile([5.0], 0.99) == 5.0
    values = list(range(1, 101))
    assert percentile(values, 0.50) == 50
    assert percentile(values, 0.99) == 99
    assert percentile(values, 1.0) == 100


def test_population_is_seeded_and_mix_tracks_weights():
    a = UserPopulation(n_users=3000, seed="pop1")
    b = UserPopulation(n_users=3000, seed="pop1")
    assert [u.profile.name for u in a.users] == \
        [u.profile.name for u in b.users]
    mix = a.mix()
    for profile in DEFAULT_PROFILES:
        assert abs(mix[profile.name] - profile.weight) < 0.05, mix
    # a different seed is a different population
    c = UserPopulation(n_users=3000, seed="pop2")
    assert [u.activity for u in a.users] != [u.activity for u in c.users]


def test_diurnal_curve_shape_and_spikes():
    curve = DiurnalCurve(amplitude=0.5, spikes=2, spike_mult=4.0,
                         seed="curve1")
    # trough at the start, peak mid-run (modulo spike windows)
    in_spike = [frac for frac in (i / 100 for i in range(101))
                if any(s <= frac < e for s, e in curve.spike_windows)]
    assert curve.multiplier(0.0) == pytest.approx(0.5)
    assert curve.multiplier(0.5) == pytest.approx(1.5)
    assert len(curve.spike_windows) == 2
    for frac in in_spike:
        base = 1.0 + 0.5 * __import__("math").sin(
            2.0 * __import__("math").pi * (frac - 0.25))
        assert curve.multiplier(frac) == pytest.approx(base * 4.0)
    # determinism
    again = DiurnalCurve(amplitude=0.5, spikes=2, spike_mult=4.0,
                         seed="curve1")
    assert again.spike_windows == curve.spike_windows


def test_schedule_is_deterministic_and_carries_deadlines():
    pop = UserPopulation(n_users=500, seed="s")
    curve = DiurnalCurve(seed="s")
    a = generate_schedule(pop, curve, duration_s=4.0, rate_jobs_s=30,
                          seed="s")
    b = generate_schedule(pop, curve, duration_s=4.0, rate_jobs_s=30,
                          seed="s")
    assert [(x.at_s, x.job["id"], x.workload) for x in a] == \
        [(y.at_s, y.job["id"], y.workload) for y in b]
    assert len(a) > 50
    by_name = {p.name: p for p in DEFAULT_PROFILES}
    for item in a:
        profile = by_name[item.workload]
        assert item.job["deadline_s"] == profile.deadline_s
        assert profile.steps[0] <= item.job["num_inference_steps"] \
            <= profile.steps[1]
        assert 0.0 <= item.at_s < 4.0
    # ids are unique (the zero-loss accounting key)
    ids = [x.job["id"] for x in a]
    assert len(ids) == len(set(ids))


def test_synthetic_executor_is_deterministic_per_attempt():
    async def run():
        ex_a = SyntheticExecutor(seed="e")
        ex_b = SyntheticExecutor(seed="e")
        job = {"id": "j1", "workflow": "img2img"}
        ra = await ex_a.do_work(dict(job), None, None)
        rb = await ex_b.do_work(dict(job), None, None)
        assert ra["pipeline_config"] == rb["pipeline_config"]
        assert ex_a._service(dict(job)) == ex_b._service(dict(job))
    asyncio.run(run())


def test_reconcile_flags_missing_and_double_settles():
    clock = [0.0]
    hive = LoadHive(lease_s=10.0, clock=lambda: clock[0])
    hive.submit_job({"id": "a"})
    hive.submit_job({"id": "b"})
    hive._take_jobs("w")
    hive._record_result({"id": "a", "artifacts": {},
                         "pipeline_config": {}}, "w")
    partial = reconcile(hive, ["a", "b"])
    assert partial["missing"] == ["b"] and not partial["zero_loss"]
    hive._record_result({"id": "b", "artifacts": {},
                         "pipeline_config": {}}, "w")
    full = reconcile(hive, ["a", "b"])
    assert full["zero_loss"] and full["completed"] == 2


# ---------------------------------------------------------------------------
# sweep pins: shipped defaults == default-seed sweep winners
# ---------------------------------------------------------------------------


def test_lane_gain_sweep_pins_shipped_defaults():
    """The ISSUE-9 satellite contract: LaneWidthController's default
    gains ARE the swarmload sweep winner (seed "swarmload"). If a
    future change re-tunes the simulator or the gains, both must move
    together — re-run the sweep and land its winner.

    Since ISSUE 27 (a lone row takes a lane 2 -> 1 after ``patience``
    boundaries) the pin holds the gain PAIR to the winner's and the
    shipped triple to within ``LANE_SWEEP_RESOLUTION`` of its cost: the
    score charges a resize nothing, so the three patience values of one
    pair lie 0.03% apart (patience 2 leads since; 6 led before, by
    0.004%), and ISSUE 27 holds decisions at widths >= 4 to PR 26's."""
    sweep = loadgen.sweep_lane_gains("swarmload")
    assert sweep["defaults_match_winner"], (
        f"shipped defaults {sweep['defaults']} != sweep winner "
        f"{sweep['winner']} (gap {sweep['defaults_cost_gap']})")
    # the table is deterministic and fully ranked
    again = loadgen.sweep_lane_gains("swarmload")
    assert again["table"] == sweep["table"]
    costs = [row["cost"] for row in sweep["table"]]
    assert costs == sorted(costs)


def test_prefetch_window_sweep_pins_shipped_default():
    sweep = loadgen.sweep_prefetch_window("swarmload")
    assert sweep["defaults_match_winner"], sweep
    from chiaswarm_tpu.serving.residency import PREFETCH_RANK_WINDOW_S

    assert sweep["default_window_s"] == PREFETCH_RANK_WINDOW_S


def test_lane_simulator_grows_under_burst_and_idles_down():
    trace = [0] * 50 + [12] + [0] * 200   # one burst into an idle lane
    out = loadgen.simulate_lane_controller(grow_at=0.75, shrink_at=0.25,
                                           patience=6, trace=trace)
    assert out["resizes"] >= 2            # grew for the burst, shrank after
    assert 0.0 <= out["padding_waste"] <= 1.0
    assert out["cost"] > 0.0


# ---------------------------------------------------------------------------
# load smoke (the fast CI leg) + THE acceptance gate
# ---------------------------------------------------------------------------


def test_load_smoke_seeded_zero_loss():
    """Fast-tier smoke: a small seeded diurnal run (modest overload)
    through 2 overload-controlled workers settles every job exactly
    once and stamps a capacity model."""
    seed = "load-smoke"
    schedule = build_scenario(seed=seed, n_users=300, duration_s=2.0,
                              rate_jobs_s=25)
    assert len(schedule) > 20
    report = asyncio.run(run_load(schedule, n_workers=2, seed=seed,
                                  lease_s=3.0, settle_timeout_s=120))
    assert report["reconciliation"]["zero_loss"], report["reconciliation"]
    capacity = report["capacity"]
    assert capacity["chips"] == 2
    assert capacity["jobs_per_s_per_chip"] > 0
    assert set(capacity["workload_mix"]) <= {p.name
                                             for p in DEFAULT_PROFILES}
    assert report["hive"]["pending"] == 0
    # the measured suggested-deadline table (ISSUE 10 satellite) rides
    # every report: per-family p99 x margin over completed-ok jobs
    suggested = report["suggested_deadlines"]
    assert suggested["margin"] == loadgen.DEADLINE_MARGIN
    families = suggested["families"]
    assert families, suggested
    for entry in families.values():
        assert entry["suggested_s"] == pytest.approx(
            entry["p99_s"] * loadgen.DEADLINE_MARGIN, rel=1e-3)
        assert entry["n"] > 0
        # the conformance satellite (ISSUE 13): each family names its
        # dominant overshoot phase (None when nothing missed)
        assert "dominant_overshoot_phase" in entry
    # swarmsight (ISSUE 13): per-family deadline-budget attribution
    # folded from the flight records — the synthetic service model
    # books as "steps", so steps must dominate every family's share —
    # plus the /api/fleet aggregate snapshot the autoscaler reads
    from chiaswarm_tpu.obs.flight import ATTRIBUTION_PHASES

    attribution = report["budget_attribution"]["families"]
    assert attribution, report["budget_attribution"]
    for family, entry in attribution.items():
        assert set(entry["mean_s"]) == set(ATTRIBUTION_PHASES), family
        assert entry["n"] > 0
        assert entry["dominant_phase"] == "steps", entry
        assert abs(sum(entry["share"].values()) - 1.0) < 0.02
    fleet = report["fleet"]
    assert fleet["aggregate"]["workers_reporting"] == 2
    assert fleet["aggregate"]["chips_in_service"] == 2
    # every settled job left a COMPLETE flight record (ISSUE 13
    # satellite — the soak legs assert the same at scale)
    hive_stats = report["hive"]
    assert hive_stats["flights"]["records"] > 0


def test_load_churn_roster_join_leave():
    """ISSUE 14 satellite (ROADMAP item 5 residue): a scripted roster —
    one worker JOINS mid-run, one LEAVES by graceful drain — keeps
    zero-loss exactly-once settlement, records both churn events, and
    the fleet plane + capacity model see the elastic roster (the
    joined worker reports; the departed one drops out of the live
    aggregate), not just a static fleet."""
    seed = "load-churn"
    schedule = build_scenario(seed=seed, n_users=300, duration_s=2.5,
                              rate_jobs_s=25)
    hive = LoadHive(lease_s=3.0, delay_s=0.0, max_attempts=4,
                    max_jobs_per_poll=2)
    report = asyncio.run(run_load(
        schedule, n_workers=2, seed=seed, hive=hive,
        roster=RosterPlan(join_at=(0.25,), leave_at=(0.6,)),
        settle_timeout_s=120))
    assert report["reconciliation"]["zero_loss"], report["reconciliation"]
    events = report["roster"]
    assert [e["action"] for e in events] == ["join", "leave"]
    joined, departed = events[0]["worker"], events[1]["worker"]
    assert joined != departed
    assert events[0]["at_job"] <= events[1]["at_job"]
    assert events[1]["drained"] is True  # a leave is a DRAIN, not a kill
    # the joined worker actually served: it reports in the fleet
    # per-worker map and settled at least one job
    assert joined in report["fleet"]["workers"]
    settlers = {str(r.get("worker_name") or "") for r in hive.results}
    assert joined in settlers, sorted(settlers)
    # the departed worker served before its drain, and the drain is not
    # a kill: every job it held completed and uploaded (zero-loss above
    # already proves exactly-once; nothing is left pending or leased)
    assert departed in settlers, sorted(settlers)
    hive_stats = report["hive"]
    assert hive_stats["pending"] == 0 and not hive_stats["leased"]
    assert report["capacity"]["jobs_per_s_per_chip"] > 0


def test_overload_gate_10x_mixed_kill():
    """THE ISSUE-9 acceptance gate: scripted 10x offered load (peak
    rate ~10x the 3-worker fleet's measured capacity), the full mixed
    workload, one worker killed mid-run. Every job settles exactly once
    — completed, shed-redispatched, or abandoned-by-policy, zero lost —
    sheds and backpressure demonstrably engaged, brownout tripped, and
    the p99 end-to-end latency of ADMITTED jobs sits within each
    workload's deadline.

    Deflaked (ISSUE 12 satellite): the gate's bounds are RATIOS of the
    issued volume and the deadline clause scales by the run's MEASURED
    host-contention factor (loadgen's in-run sleep-overshoot probe) —
    absolute shed counts and raw wall clock flaked on contended CI
    hosts while asserting nothing the ratios don't. The zero-loss and
    exactly-once invariants are untouched."""
    seed = "overload-gate"
    # ~650 jobs over 3 s: mean service ~0.12 s x 3 single-slot workers
    # ≈ 22 jobs/s capacity vs ~200 jobs/s offered at the diurnal peak
    schedule = build_scenario(seed=seed, n_users=800, duration_s=3.0,
                              rate_jobs_s=160)
    assert len(schedule) > 400
    t0 = time.monotonic()
    report = asyncio.run(run_load(
        schedule, n_workers=3, seed=seed, lease_s=3.0,
        max_jobs_per_poll=4, kill=KillPlan(after_frac=0.5),
        settle_timeout_s=240))
    wall = time.monotonic() - t0
    issued_n = len(schedule)
    contention = report["contention"]["factor"]

    # 1. zero job loss, exactly once (the invariants stay absolute)
    rec = report["reconciliation"]
    assert rec["zero_loss"], rec
    assert rec["issued"] == issued_n

    # 2. the kill landed and the fleet absorbed it
    assert report["kill"] and report["kill"]["jobs"], report["kill"]
    assert report["hive"]["metrics"][
        "chiaswarm_hive_jobs_redelivered_total"]["values"][""] >= 0

    # 3. overload control engaged: sheds settled, backpressure waited,
    #    and at least one worker browned out. Ratio bounds: at 10x
    #    offered load the fleet MUST shed most of the volume whatever
    #    the host speed — a slower host sheds more, never fewer.
    outcomes = report["outcomes"]
    assert outcomes["shed"] > 0.05 * issued_n, outcomes
    assert outcomes["ok"] > 0.05 * issued_n, outcomes
    workers = report["workers"].values()
    assert sum(w["jobs_shed"] for w in workers) > 0.1 * issued_n
    assert sum(w["polls_backpressured"] for w in workers) > 0
    assert any(w["overload"]["sheds_total"] > 0 for w in workers)
    # shed jobs are capacity decisions, never failures
    assert all(w["jobs_failed"] == 0 for w in workers)

    # 4. THE latency clause, contention-adjusted: p99 of admitted jobs'
    #    latency/deadline ratios within the measured sleep-stretch
    #    factor (== 1.0 on an idle host, so the clause is unchanged
    #    there; a contended host loosens it by exactly what the host
    #    stole, not by an arbitrary fudge)
    assert report["admitted_deadline"][
        "p99_within_deadline_contention_adjusted"], (
        report["admitted_deadline"], report["contention"])

    # 5. the capacity model is populated
    capacity = report["capacity"]
    assert capacity["chips"] == 3
    assert capacity["jobs_per_s_per_chip"] > 0
    assert capacity["models_resident"] >= 1
    assert abs(sum(capacity["workload_mix"].values()) - 1.0) < 0.01
    # the run stays CI-sized relative to the host: shedding keeps the
    # backlog from serializing 10x load through 3 slots
    assert wall < 180 * contention, (wall, contention)


# ---------------------------------------------------------------------------
# per-model-family deadline tables (ISSUE 10 satellite, ROADMAP 5b)
# ---------------------------------------------------------------------------


def test_family_deadline_defaults_pinned_to_sweep():
    """The shipped DEFAULT_FAMILY_DEADLINES must equal the default-seed
    sweep derivation — pinned defaults == winner, the PR-9 convention
    (a default and the harness can never silently disagree)."""
    assert loadgen.DEFAULT_FAMILY_DEADLINES == \
        loadgen.sweep_deadline_table()
    # sanity of the derivation itself: deterministic per seed, scales
    # with the family cost factor, margin applied over the p99
    again = loadgen.sweep_deadline_table()
    assert again == loadgen.DEFAULT_FAMILY_DEADLINES
    table = loadgen.DEFAULT_FAMILY_DEADLINES
    assert table["tiny"] < table["sd15"] < table["sdxl"]
    # the few-step-distilled classes (ISSUE 12) price at their base
    # family's per-step cost x ~4/30 of the steps — always cheaper
    # than their full-step parent
    assert table["tiny"] < table["sdxl_turbo"] < table["sd15"]
    assert table["tiny"] < table["sd_turbo"] < table["sd15"]
    assert table["sd_turbo"] < table["sdxl_turbo"]


def test_model_family_heuristic():
    assert loadgen.model_family("stabilityai/sdxl-base") == "sdxl"
    assert loadgen.model_family("tiny") == "tiny"
    assert loadgen.model_family("swarm/sd15") == "sd15"
    assert loadgen.model_family(None) == "sd15"
    # few-step-distilled names outrank the "xl" hint (ISSUE 12), and
    # non-XL distillations price at the SD-class per-step cost
    assert loadgen.model_family("stabilityai/sdxl-turbo") == "sdxl_turbo"
    assert loadgen.model_family("latent-consistency/lcm-lora-sdxl") == \
        "sdxl_turbo"
    assert loadgen.model_family("stabilityai/sd-turbo") == "sd_turbo"
    assert loadgen.model_family("sd15-lcm") == "sd_turbo"


def test_fewstep_traffic_class_in_default_mix():
    """The txt2img_fewstep class (ISSUE 12): present in the default
    population mix, SHORT-deadline (the tightest in the mix), few-step
    (2–8), and scheduled jobs carry its deadline + step bounds."""
    by_name = {p.name: p for p in DEFAULT_PROFILES}
    fewstep = by_name["txt2img_fewstep"]
    assert fewstep.deadline_s == min(p.deadline_s
                                     for p in DEFAULT_PROFILES)
    assert fewstep.steps == (2, 8)
    pop = UserPopulation(n_users=2000, seed="fewstep")
    assert abs(pop.mix()["txt2img_fewstep"] - fewstep.weight) < 0.05
    schedule = generate_schedule(pop, DiurnalCurve(seed="fewstep"),
                                 duration_s=4.0, rate_jobs_s=40,
                                 seed="fewstep")
    fewstep_jobs = [s for s in schedule
                    if s.workload == "txt2img_fewstep"]
    assert fewstep_jobs, "mix produced no few-step arrivals"
    for item in fewstep_jobs:
        assert item.job["deadline_s"] == fewstep.deadline_s
        assert 2 <= item.job["num_inference_steps"] <= 8
        # the class IS the lcm-kind CFG-free path: real-pipeline runs
        # must exercise the fewstep lane eligibility, not a short dpm
        # job wearing the class name
        assert item.job["guidance_scale"] == 1.0
        assert item.job["parameters"]["scheduler_type"] == "LCMScheduler"


def test_worker_honors_family_deadline_override():
    """The settings-side half: ``family_deadline_s`` slots between a
    job's explicit deadline_s and the per-workflow table
    (node/worker.py::_job_deadline_s)."""
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    class StubSlot:
        depth = 2
        data_width = 1

        def descriptor(self):
            return "stub"

    worker = Worker(
        settings=Settings(hive_uri="http://h", hive_token="t",
                          worker_name="deadline-w",
                          install_signal_handlers=False,
                          job_deadline_s=600.0,
                          family_deadline_s={"tiny": 42.0}),
        pool=[StubSlot()],
        registry=ModelRegistry(catalog=[], allow_random=True))
    # family override engages for a catalog-resolvable model name
    assert worker._job_deadline_s({"model_name": "tiny"}) == 42.0
    # the job's explicit deadline always wins
    assert worker._job_deadline_s(
        {"model_name": "tiny", "deadline_s": 7.5}) == 7.5
    # a family not in the table falls through to the workflow default
    # (unknown names resolve to the sd15 family via get_family)
    assert worker._job_deadline_s(
        {"model_name": "no/such-family-model"}) == 600.0
    no_table = Worker(
        settings=Settings(hive_uri="http://h", hive_token="t",
                          worker_name="deadline-x",
                          install_signal_handlers=False,
                          job_deadline_s=123.0),
        pool=[StubSlot()],
        registry=ModelRegistry(catalog=[], allow_random=True))
    assert no_table._job_deadline_s({"model_name": "tiny"}) == 123.0


# ---------------------------------------------------------------------------
# nightly REAL-lane load soak (ISSUE 10 satellite, ROADMAP 5a):
# the harness's control-plane numbers meet the compute plane — real
# tiny-family lanes behind the same worker_factory seam
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_real_lane_load_soak_tiny_family(monkeypatch):
    """Swap the SyntheticExecutor for REAL tiny-family lanes via the
    worker_factory seam: a seeded diurnal stream of txt2img jobs runs
    through two workers with real pools/registries (lanes default-on),
    every job settles exactly once, and real frames come back."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    seed = os.environ.get("CHIASWARM_SOAK_SEED", "real-lane-default")
    jobs_scale = int(os.environ.get("CHIASWARM_SOAK_JOBS", "120"))
    # real compiles are the cost driver: a handful of jobs exercises
    # the whole path (poll -> format -> lane -> decode -> upload)
    profiles = (loadgen.WorkloadProfile("txt2img", 1.0, 60.0, (2, 4),
                                        0.5),)
    population = UserPopulation(n_users=50, profiles=profiles,
                                models=("tiny",),
                                seed=f"real:{seed}")
    curve = DiurnalCurve(seed=f"real:{seed}")
    schedule = generate_schedule(
        population, curve, duration_s=2.0,
        rate_jobs_s=max(3.0, jobs_scale / 30.0),
        seed=f"real:{seed}", id_prefix="real",
        content_type="image/png")
    assert schedule, "seeded schedule came out empty"

    def factory(uri: str, name: str) -> Worker:
        pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                        devices=jax.devices()[:1])
        return Worker(
            settings=Settings(
                hive_uri=uri, hive_token="t", worker_name=name,
                job_deadline_s=600.0, heartbeat_s=0.1,
                poll_busy_s=0.02, poll_idle_s=0.05,
                poll_backoff_base_s=0.02, poll_backoff_cap_s=0.2,
                upload_retries=5, upload_retry_delay_s=0.02,
                drain_timeout_s=60.0, result_drain_timeout_s=30.0,
                install_signal_handlers=False),
            registry=ModelRegistry(
                catalog=[{"name": "tiny", "family": "tiny",
                          "parameters": {}}],
                allow_random=True),
            pool=pool)

    hive = LoadHive(lease_s=120.0, delay_s=0.0, max_attempts=4,
                    max_jobs_per_poll=1)
    report = asyncio.run(run_load(
        schedule, n_workers=2, worker_factory=factory, hive=hive,
        seed=f"real:{seed}", settle_timeout_s=900))
    rec = report["reconciliation"]
    assert rec["zero_loss"], rec
    assert report["outcomes"]["ok"] == len(schedule), report["outcomes"]
    assert report["capacity"]["jobs_per_s_per_chip"] > 0
    # the suggested-deadline table now reflects MEASURED tiny-family
    # latencies — the live refinement of the shipped sweep defaults
    assert "tiny" in report["suggested_deadlines"]["families"]
    # swarmsight (ISSUE 13 satellite): every settled REAL-lane soak job
    # has a complete flight record, and the real-pipeline digests carry
    # lane step spans the budget attribution books as steps
    assert hive.flights.verify(list(hive.completed)) == []
    attribution = report["budget_attribution"]["families"]
    assert attribution["tiny"]["mean_s"]["steps"] > 0, attribution


# ---------------------------------------------------------------------------
# nightly diurnal fleet soak (chaos-soak.yml; seed = run id)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_load_soak_diurnal_fleet_kill():
    """Nightly soak: one diurnal-curve fleet run at soak scale, seeded
    from the run id (CHIASWARM_SOAK_SEED) for exact replay, with a
    mid-run worker kill AND a scripted roster churn leg (ISSUE 14
    satellite): one worker joins mid-run, one drains and leaves. Gate:
    zero loss + admitted-deadline p99 under the elastic fleet."""
    seed = os.environ.get("CHIASWARM_SOAK_SEED", "load-soak-default")
    jobs_scale = int(os.environ.get("CHIASWARM_SOAK_JOBS", "120"))
    schedule = build_scenario(seed=f"load-soak:{seed}", n_users=2000,
                              duration_s=6.0,
                              rate_jobs_s=max(20, jobs_scale // 3))
    hive = LoadHive(lease_s=4.0, delay_s=0.0, max_attempts=4,
                    max_jobs_per_poll=4)
    report = asyncio.run(run_load(
        schedule, n_workers=3, seed=f"load-soak:{seed}", hive=hive,
        kill=KillPlan(after_frac=0.4),
        roster=RosterPlan(join_at=(0.3,), leave_at=(0.7,)),
        settle_timeout_s=600))
    assert report["reconciliation"]["zero_loss"], report["reconciliation"]
    # the churn leg actually churned: both events recorded, and the
    # kill victim was never the leave candidate (the plan skips it)
    assert [e["action"] for e in report["roster"]] == ["join", "leave"]
    if report["kill"]:
        assert report["roster"][1]["worker"] != report["kill"]["worker"]
    assert report["admitted_deadline"]["p99_within_deadline"], \
        report["admitted_deadline"]
    assert report["capacity"]["jobs_per_s_per_chip"] > 0
    # every settled envelope is a classified outcome the taxonomy knows
    hive_stats = report["hive"]
    assert hive_stats["pending"] == 0 and not hive_stats["leased"]
    # swarmsight (ISSUE 13 satellite): every SETTLED soak job left a
    # complete flight record (no orphan spans, no attempt gaps);
    # abandoned-by-policy jobs keep their unsettled records
    assert hive.flights.verify(list(hive.completed)) == []
