"""swarmscope suite (ISSUE 4): metrics registry semantics, Prometheus
exposition, span-tree construction across threads, trace-ring eviction,
the worker's /metrics + /debug/traces endpoints, and the end-to-end
acceptance gate: a tiny txt2img job through a REAL worker — stepper
opted out and on (the ISSUE-7 default) — must yield a trace whose span
tree nests
poll/execute/encode/step/decode/upload with positive durations,
exported as Perfetto-loadable JSON.
"""

from __future__ import annotations

import asyncio
import json
import threading

import pytest

from chiaswarm_tpu.obs import metrics as obs_metrics
from chiaswarm_tpu.obs import trace as obs_trace
from chiaswarm_tpu.obs.metrics import Registry, render_all
from chiaswarm_tpu.obs.trace import JobTrace, TraceRing, span


@pytest.fixture(autouse=True)
def _tmp_root(tmp_path, monkeypatch):
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path))
    return tmp_path


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_semantics():
    reg = Registry()
    jobs = reg.counter("jobs_total", "jobs", labelnames=("outcome",))
    jobs.inc(outcome="ok")
    jobs.inc(2, outcome="ok")
    jobs.inc(outcome="error")
    assert jobs.value(outcome="ok") == 3
    assert jobs.value(outcome="error") == 1
    assert jobs.value(outcome="never") == 0
    with pytest.raises(ValueError):
        jobs.inc(-1, outcome="ok")  # counters only go up
    with pytest.raises(ValueError):
        jobs.inc(bogus="label")  # undeclared label set

    depth = reg.gauge("queue_depth", "depth")
    depth.set(7)
    depth.dec(3)
    assert depth.value() == 4

    lat = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.7, 5.0, 50.0):
        lat.observe(v)
    assert lat.count() == 5
    assert lat.sum() == pytest.approx(56.25)

    # get-or-create: same object back; type/label mismatch raises
    assert reg.counter("jobs_total", labelnames=("outcome",)) is jobs
    with pytest.raises(ValueError):
        reg.gauge("jobs_total")
    with pytest.raises(ValueError):
        reg.counter("jobs_total", labelnames=("other",))

    # set_to mirrors an external monotonic total and never regresses
    done = reg.counter("done_total")
    done.set_to(10)
    done.set_to(4)
    assert done.value() == 10


def test_registry_collectors_run_at_scrape_time_and_never_raise():
    reg = Registry()
    calls = []

    def good():
        calls.append("good")
        reg.gauge("live").set(len(calls))

    def broken():
        raise RuntimeError("mirror cracked")

    reg.add_collector(good)
    reg.add_collector(broken)
    reg.render()
    snap = reg.snapshot()
    assert calls == ["good", "good"]  # once per scrape, errors contained
    assert snap["live"]["values"][""] == 2


def test_prometheus_exposition_format():
    reg = Registry()
    c = reg.counter("swarm_jobs_total", 'jobs with "quotes"\nand newline',
                    labelnames=("model",))
    c.inc(3, model='tiny "v1"\n')
    reg.gauge("swarm_depth", "queue depth").set(2.5)
    h = reg.histogram("swarm_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(9.0)
    body = reg.render()
    lines = body.splitlines()
    assert "# TYPE swarm_jobs_total counter" in lines
    # label values escape quotes and newlines per the text format
    assert 'swarm_jobs_total{model="tiny \\"v1\\"\\n"} 3' in lines
    assert "# HELP swarm_jobs_total jobs with \"quotes\"\\nand newline" \
        in lines
    assert "swarm_depth 2.5" in lines
    # histogram: cumulative le buckets, +Inf == count, sum present
    assert 'swarm_lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'swarm_lat_seconds_bucket{le="1"} 2' in lines
    assert 'swarm_lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "swarm_lat_seconds_count 3" in lines
    assert body.endswith("\n")

    # an unlabeled counter renders an explicit 0 from registration; a
    # labeled one renders its TYPE header even before any sample
    reg2 = Registry()
    reg2.counter("zero_total", "nothing yet")
    reg2.counter("labeled_total", "nothing yet", labelnames=("tag",))
    body2 = reg2.render()
    assert "zero_total 0" in body2
    assert "# TYPE labeled_total counter" in body2
    # merged scrape bodies concatenate cleanly
    merged = render_all([reg, reg2])
    assert "swarm_depth 2.5" in merged and "zero_total 0" in merged


# ---------------------------------------------------------------------------
# span trees + ring
# ---------------------------------------------------------------------------


def test_span_tree_nesting_and_ordering_across_threads():
    """The worker's cross-thread shape, faked: phases open on the event
    -loop side, pipeline spans attach from an executor thread via
    activate(), and the finished tree nests in submission order."""
    trace = JobTrace("job", id="fake-1", model="tiny")
    trace.phase("poll")

    def executor_thread():
        with obs_trace.activate(trace):
            with span("format"):
                pass
            with span("encode", batch=1):
                with span("tokenize"):
                    pass
            with span("step", steps=2):
                pass
            with span("decode"):
                pass

    trace.phase("execute")
    worker = threading.Thread(target=executor_thread)
    worker.start()
    worker.join()
    trace.phase("upload")
    ring = TraceRing(capacity=4)
    trace.finish(ring)
    trace.finish(ring)  # idempotent: one ring entry
    assert len(ring) == 1

    root = trace.root
    assert [c.name for c in root.children] == ["poll", "execute", "upload"]
    execute = root.children[1]
    assert [c.name for c in execute.children] == \
        ["format", "encode", "step", "decode"]
    assert [c.name for c in execute.find("encode").children] == ["tokenize"]
    for name in ("poll", "execute", "encode", "step", "decode", "upload"):
        node = root.find(name)
        assert node is not None and not node.open
        assert node.duration_s > 0
    # phases close their predecessor: no overlap leaks
    assert root.children[0].t1 <= root.children[1].t0 + 1e-9

    # chrome export: complete events, positive integer durations
    events = trace.to_chrome_events(tid=3)
    names = [e["name"] for e in events]
    assert names[0] == "job" and "tokenize" in names
    for event in events:
        assert event["ph"] == "X"
        assert isinstance(event["ts"], int)
        assert event["dur"] >= 1
        assert event["tid"] == 3
    # the whole document is JSON-serializable as exported
    json.dumps(ring.to_chrome())


def test_span_outside_any_trace_is_detached_and_harmless():
    with span("orphan") as orphan:
        pass
    assert orphan.duration_s > 0
    assert obs_trace.current_span() is None


def test_trace_ring_eviction_keeps_newest():
    ring = TraceRing(capacity=3)
    for i in range(5):
        trace = JobTrace("job", id=f"t{i}")
        trace.finish(ring)
    assert len(ring) == 3
    kept = [t.meta["id"] for t in ring.traces()]
    assert kept == ["t2", "t3", "t4"]
    chrome = ring.to_chrome()
    assert len(chrome["traceEvents"]) == 3
    # tree export carries the metadata
    tree = ring.to_dicts()
    assert tree[0]["root"]["meta"]["id"] == "t2"
    assert "started_at_unix" in tree[0]

    # eviction accounting + the ?since= cursor (ISSUE 13 satellite):
    # 5 pushed into capacity 3 evicts 2 root-only traces (2 spans); the
    # cursor exposes the gap a slow scraper must detect
    assert ring.traces_evicted == 2 and ring.spans_evicted == 2
    cursor = ring.cursor()
    assert cursor["last_seq"] == 5 and cursor["oldest_seq"] == 3
    assert cursor["evicted_spans"] == 2
    assert [t.meta["id"] for t in ring.traces(since=3)] == ["t3", "t4"]
    assert [t["seq"] for t in ring.to_dicts(since=3)] == [4, 5]
    assert ring.to_chrome(since=5)["traceEvents"] == []


def test_trace_rides_job_dicts_via_attach_detach():
    job = {"id": "x"}
    trace = JobTrace("job", id="x")
    obs_trace.attach(job, trace)
    assert obs_trace.job_trace(job) is trace
    assert obs_trace.detach(job) is trace
    assert obs_trace.TRACE_KEY not in job
    assert obs_trace.detach(job) is None
    assert obs_trace.job_trace(None) is None


# ---------------------------------------------------------------------------
# one primitive, two clocks (ISSUE 26): a span IS a profiler annotation
# ---------------------------------------------------------------------------


@pytest.fixture
def annotations(monkeypatch):
    """``compat.trace_annotation`` stubbed: every enter / exit, by name,
    in order. The primitive resolves the class once, so it is reset."""
    from chiaswarm_tpu.core import compat

    log = []

    class Stub:
        def __init__(self, name, **kwargs):
            assert not kwargs
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setitem(compat._cache, "trace_annotation", Stub)
    monkeypatch.setattr(obs_trace, "_annotation_cls", None)
    return log


def test_span_enters_one_annotation_named_for_it(annotations):
    # outside any job trace (the lane driver thread, the poll loop): the
    # span is a throwaway on the job's clock and still annotates
    with span("lane.step") as timed:
        assert annotations == [("enter", "swarm.lane.step")]
    assert annotations == [("enter", "swarm.lane.step"),
                           ("exit", "swarm.lane.step")]
    assert timed.duration_s > 0 and obs_trace.current_span() is None
    # closed on exception, once, and end() stays idempotent
    del annotations[:]
    with pytest.raises(RuntimeError):
        with span("png") as broken:
            raise RuntimeError("encode failed")
    broken.end()
    assert annotations == [("enter", "swarm.png"), ("exit", "swarm.png")]


def test_every_name_in_the_job_tree_is_a_name_on_the_profilers_clock(
        annotations):
    trace = JobTrace("job", id="both-clocks")
    trace.phase("poll")
    trace.phase("execute")  # closes poll on both clocks
    with obs_trace.activate(trace):
        with span("encode"), span("lane.encode"):
            pass
        with span("step") as step:
            # built from stamps after the fact: the job's clock only
            step.child_at("lane.wait", step.t0, step.t0 + 0.001)
    trace.phase("upload")
    trace.finish(TraceRing(capacity=1))
    entered = [name for kind, name in annotations if kind == "enter"]
    exited = [name for kind, name in annotations if kind == "exit"]
    assert entered == ["swarm.job", "swarm.poll", "swarm.execute",
                       "swarm.encode", "swarm.lane.encode", "swarm.step",
                       "swarm.upload"]
    assert sorted(exited) == sorted(entered)
    assert annotations.index(("exit", "swarm.poll")) \
        < annotations.index(("enter", "swarm.execute"))
    in_tree = set()

    def walk(node):
        in_tree.add("swarm." + node.name)
        for child in node.children:
            walk(child)

    walk(trace.root)
    assert in_tree - set(entered) == {"swarm.lane.wait"}


def test_a_failing_annotation_never_fails_the_span(monkeypatch):
    class Broken:
        def __init__(self, name):
            raise RuntimeError("no profiler")

    monkeypatch.setattr(obs_trace, "_annotation_cls", Broken)
    with span("decode") as timed:
        pass
    assert timed.duration_s > 0 and not timed.open


def test_children_from_explicit_stamps_export_their_offsets():
    from chiaswarm_tpu.obs.flight import span_digest

    trace = JobTrace("job", id="stamps", trace_id="t", span_id="t.1")
    trace.phase("poll")
    execute = trace.phase("execute")
    assert trace.gap("handover").t0 == execute.t0  # nothing named yet
    with obs_trace.activate(trace):
        with span("step") as step:
            t = step.t0
            step.child_at("lane.wait", t - 0.002, t + 0.010)
            step.child_at("lane.steps", t + 0.010, t + 0.250, rows=1)
            step.child_at("lane.handoff", t + 0.250, t + 0.400)
            backwards = step.child_at("never.negative", t + 0.5, t + 0.1)
    waited = trace.gap("result.wait")  # from the last child's end to now
    assert waited.t0 == step.t1 and waited.t1 >= waited.t0
    assert backwards.duration_s == 0.0
    trace.phase("upload")
    digest = span_digest(trace, worker_name="w")
    spans = {entry["name"]: entry for entry in digest["spans"]}
    base = round(step.t0 - trace.root.t0, 6)
    assert spans["lane.wait"]["t0_s"] == pytest.approx(base - 0.002,
                                                       abs=2e-6)
    assert spans["lane.wait"]["dur_s"] == pytest.approx(0.012, abs=2e-6)
    assert spans["lane.steps"]["t0_s"] == pytest.approx(base + 0.010,
                                                        abs=2e-6)
    assert spans["lane.steps"]["dur_s"] == pytest.approx(0.240, abs=2e-6)
    assert spans["lane.steps"]["meta"] == {"rows": 1}
    assert spans["lane.handoff"]["dur_s"] == pytest.approx(0.150, abs=2e-6)
    assert {spans[n]["phase"] for n in (
        "handover", "step", "lane.wait", "result.wait")} == {"execute"}
    assert trace.gap("after.the.job") is not None  # upload is open
    trace.finish(TraceRing(capacity=1))
    assert trace.gap("nothing.open") is None


# ---------------------------------------------------------------------------
# profiler hooks (unit level; the capture endpoint is covered below)
# ---------------------------------------------------------------------------


def test_profiler_capture_and_job_profile_with_stub_backend(
        tmp_path, monkeypatch):
    from chiaswarm_tpu.core import compat
    from chiaswarm_tpu.obs import profiling

    calls = []
    monkeypatch.setitem(compat._cache, "profiler_start_trace",
                        lambda target: calls.append(("start", target)))
    monkeypatch.setitem(compat._cache, "profiler_stop_trace",
                        lambda: calls.append(("stop",)))
    out = profiling.capture(0.01, out=str(tmp_path / "prof"))
    assert out["status"] == "ok"
    assert calls[0][0] == "start" and calls[-1] == ("stop",)
    assert out["dir"].startswith(str(tmp_path / "prof"))

    class StubTrace:
        def __init__(self, target):
            calls.append(("job", target))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setitem(compat._cache, "profiler_trace", StubTrace)
    monkeypatch.setenv(profiling.PROFILE_DIR_ENV, str(tmp_path / "jobs"))
    with profiling.job_profile("job-7") as active:
        assert active is True
    assert calls[-1] == ("job", str(tmp_path / "jobs" / "job-7"))

    monkeypatch.delenv(profiling.PROFILE_DIR_ENV)
    with profiling.job_profile("job-8") as active:
        assert active is False  # opt-in: no dir, no trace
    assert profiling.capture(0.01)["status"] == "error"  # no dir either


# ---------------------------------------------------------------------------
# worker endpoints (/metrics, /debug/traces, /debug/profile, /healthz)
# ---------------------------------------------------------------------------


def _endpoint_settings(uri: str):
    from chiaswarm_tpu.node.settings import Settings

    return Settings(
        hive_uri=uri, hive_token="t", worker_name="obs-worker",
        health_bind_ephemeral=True, install_signal_handlers=False,
        job_deadline_s=600.0, poll_busy_s=0.02, poll_idle_s=0.05,
        poll_backoff_base_s=0.02, poll_backoff_cap_s=0.1,
        upload_retries=2, upload_retry_delay_s=0.01,
        drain_timeout_s=5.0, result_drain_timeout_s=5.0)


def test_worker_serves_metrics_and_traces_endpoints():
    """The health app (loopback) grows /metrics (Prometheus text,
    resilience + stepper + compile-cache families), /debug/traces
    (Perfetto JSON from the worker's ring), and /debug/profile
    (validated, explicit errors) — while /healthz keeps its JSON keys
    as the read-through view."""
    import aiohttp

    from chiaswarm_tpu.node.chaos import ChaoticExecutor, ChaoticHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.worker import Worker

    class StubSlot:
        depth = 2
        data_width = 1

        def descriptor(self):
            return "stub"

    async def scenario():
        hive = ChaoticHive()
        uri = await hive.start()
        hive.submit({"id": "m-ok", "model_name": "m/ok", "prompt": "p",
                     "content_type": "application/json"})
        hive.submit({"id": "m-err", "model_name": "m/err", "prompt": "p",
                     "chaos": ["crash"],
                     "content_type": "application/json"})
        worker = Worker(settings=_endpoint_settings(uri),
                        pool=[StubSlot()],
                        registry=ModelRegistry(catalog=[],
                                               allow_random=True),
                        executor=ChaoticExecutor())
        task = asyncio.create_task(worker.run())
        try:
            await hive.wait_for_results(2, timeout=30)
            for _ in range(100):
                if getattr(worker, "health_address", None):
                    break
                await asyncio.sleep(0.05)
            host, port = worker.health_address
            base = f"http://{host}:{port}"
            async with aiohttp.ClientSession() as session:
                async with session.get(f"{base}/healthz") as resp:
                    health = await resp.json()
                async with session.get(f"{base}/metrics") as resp:
                    assert resp.status == 200
                    assert resp.headers["Content-Type"].startswith(
                        "text/plain")
                    metrics_body = await resp.text()
                async with session.get(f"{base}/debug/traces") as resp:
                    chrome = await resp.json()
                async with session.get(
                        f"{base}/debug/traces?format=tree") as resp:
                    tree = await resp.json()
                # ISSUE 13 satellite: the ?since= scrape cursor — a
                # caught-up scraper gets zero traces back, a bad value
                # is an explicit 400, and the cursor block carries the
                # eviction counters gap detection needs
                async with session.get(f"{base}/debug/traces"
                                       f"?format=tree&since=0") as resp:
                    tree_since = await resp.json()
                last_seq = tree_since["cursor"]["last_seq"]
                async with session.get(
                        f"{base}/debug/traces?format=tree"
                        f"&since={last_seq}") as resp:
                    tree_tail = await resp.json()
                async with session.get(
                        f"{base}/debug/traces?since=abc") as resp:
                    assert resp.status == 400
                async with session.get(
                        f"{base}/debug/profile?seconds=abc") as resp:
                    assert resp.status == 400
                async with session.get(
                        f"{base}/debug/profile?seconds=0.2") as resp:
                    # no CHIASWARM_PROFILE_DIR and no ?dir= -> explicit
                    # error, never a crash
                    assert resp.status == 500
                    assert (await resp.json())["status"] == "error"
                # swarmlens (ISSUE 11): the numerics flight-recorder view
                async with session.get(f"{base}/debug/numerics") as resp:
                    assert resp.status == 200
                    numerics_payload = await resp.json()
                async with session.get(
                        f"{base}/debug/numerics?limit=abc") as resp:
                    assert resp.status == 400
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)
            await hive.stop()
        return (health, metrics_body, chrome, tree, tree_since,
                tree_tail, numerics_payload, worker)

    (health, body, chrome, tree, tree_since, tree_tail,
     numerics_payload, worker) = asyncio.run(scenario())

    # the scrape cursor (ISSUE 13): since=0 returns both traces with
    # their ring seqs; since=last returns none; nothing evicted yet so
    # the counter reads zero and the oldest seq is still 1
    assert len(tree_since["traces"]) == 2
    assert [t["seq"] for t in tree_since["traces"]] == [1, 2]
    assert tree_since["cursor"]["last_seq"] == 2
    assert tree_since["cursor"]["oldest_seq"] == 1
    assert tree_since["cursor"]["evicted_spans"] == 0
    assert tree_tail["traces"] == []
    assert tree_tail["cursor"]["last_seq"] == 2

    # /debug/numerics: the payload distinguishes "empty because taps are
    # off" from "empty because nothing recorded" — CHIASWARM_NUMERICS is
    # unset in the suite, so enabled=False and the ring is bounded+empty
    assert numerics_payload["enabled"] is False
    assert numerics_payload["records"] == []
    assert numerics_payload["ring"]["capacity"] >= 1
    assert "traced_probes" in numerics_payload
    # the measured hang-budget suggestion rides /healthz guard (ISSUE
    # 11 satellite): with no lane steps yet it reports measured=False
    # and the CURRENT prior knobs, never invented numbers
    suggestion = health["guard"]["suggested_hang_budget"]
    assert suggestion["measured"] in (False, True)
    assert "current" in suggestion

    # /healthz read-through view unchanged (PR-2/PR-3 keys intact)
    for key in ("jobs_failed", "jobs_retried", "results_dead_lettered",
                "breakers", "dead_letter_depth", "stepper"):
        assert key in health
    assert health["jobs_failed"] == 1

    # /metrics: resilience counters migrated onto the registry...
    assert "chiaswarm_jobs_failed_total 1" in body
    assert 'chiaswarm_jobs_total{outcome="error"} 1' in body
    assert 'chiaswarm_jobs_total{outcome="ok"} 1' in body
    # ...stepper-lane families (lanes are default-ON since ISSUE 7)...
    assert "chiaswarm_stepper_steps_executed_total" in body
    assert "chiaswarm_stepper_enabled 1" in body
    # ...the adaptive-width control-loop families (ISSUE 7): resize
    # actions by direction, the arrival-rate demand gauge, and the
    # per-workload admission breadth — all present from scrape one
    # (values are process-cumulative, so assert the series, not 0)
    assert "# TYPE chiaswarm_stepper_lane_resizes_total counter" in body
    assert 'chiaswarm_stepper_lane_resizes_total{direction="grow"}' in body
    assert ('chiaswarm_stepper_lane_resizes_total{direction="shrink"}'
            in body)
    assert "# TYPE chiaswarm_stepper_arrival_rate gauge" in body
    assert ("# TYPE chiaswarm_stepper_lane_admissions_total counter"
            in body)
    for workload in ("txt2img", "img2img", "inpaint", "controlnet"):
        assert (f'chiaswarm_stepper_lane_admissions_total'
                f'{{workload="{workload}"}}' in body), workload
    # ...lease/checkpoint/resume families (ISSUE 6) exist from scrape
    # one, even before any fleet event — dashboards need the zeroes...
    assert "chiaswarm_lease_heartbeats_total 0" in body
    assert "chiaswarm_leases_lost_total 0" in body
    assert "chiaswarm_checkpoints_written_total 0" in body
    assert "chiaswarm_checkpoints_corrupt_total 0" in body
    assert "chiaswarm_checkpoint_depth 0" in body
    assert "chiaswarm_inflight_jobs 0" in body
    assert "chiaswarm_stepper_rows_resumed_total 0" in body
    assert "# TYPE chiaswarm_stepper_resume_step histogram" in body
    # ...HBM residency families (ISSUE 8, serving/residency.py): every
    # label vocabulary pre-seeded to zero from scrape one...
    assert "# TYPE chiaswarm_residency_resident_bytes gauge" in body
    assert "chiaswarm_residency_budget_bytes" in body
    assert "chiaswarm_residency_peak_bytes" in body
    assert "chiaswarm_residency_bounces_total" in body
    from chiaswarm_tpu.obs.metrics import (
        RESIDENCY_EVICT_REASONS,
        RESIDENCY_LOAD_MODES,
        RESIDENCY_STATES,
    )

    for state in RESIDENCY_STATES:
        assert f'chiaswarm_residency_models{{state="{state}"}}' in body
    for reason in RESIDENCY_EVICT_REASONS:
        assert (f'chiaswarm_residency_evictions_total{{reason="{reason}"}}'
                in body)
    for mode in RESIDENCY_LOAD_MODES:
        assert (f'chiaswarm_residency_loads_total{{mode="{mode}"}}'
                in body)
    assert "# TYPE chiaswarm_residency_load_seconds histogram" in body
    # ...overload-control families (ISSUE 9, node/overload.py): the
    # shed/backpressure counters live on the worker registry DISTINCT
    # from the failure counters, pre-seeded from scrape one...
    assert "chiaswarm_jobs_shed_total 0" in body
    assert "chiaswarm_polls_backpressured_total 0" in body
    assert "chiaswarm_overload_state 0" in body
    assert "chiaswarm_overload_admission_cap 0" in body
    assert "chiaswarm_overload_backpressure_waits_total 0" in body
    assert ("# TYPE chiaswarm_overload_predicted_wait_seconds histogram"
            in body)
    for workload in ("txt2img", "img2img", "inpaint", "controlnet"):
        assert (f'chiaswarm_overload_shed_total{{workload="{workload}"}} 0'
                in body), workload
    assert "overload" in health and health["overload"]["state"] == "normal"
    # ...swarmguard families (ISSUE 10, serving/guard.py): hang/rung
    # counters pre-seeded across their vocabularies, the condemned-lane
    # and quarantine series at zero, the health/invalid families
    # declared — all from scrape one, before any gray failure...
    from chiaswarm_tpu.serving.guard import HANG_PHASES, HEAL_RUNGS

    for phase in HANG_PHASES:
        assert f'chiaswarm_guard_hangs_total{{phase="{phase}"}} 0' \
            in body, phase
    for rung in HEAL_RUNGS:
        assert f'chiaswarm_guard_heal_rung_total{{rung="{rung}"}} 0' \
            in body, rung
    assert "chiaswarm_guard_condemned_lanes_total 0" in body
    assert "chiaswarm_guard_quarantined_devices 0" in body
    assert "# TYPE chiaswarm_guard_invalid_outputs_total counter" in body
    assert "# TYPE chiaswarm_guard_device_health gauge" in body
    assert "chiaswarm_stepper_lanes_condemned_total 0" in body
    assert "chiaswarm_stepper_rows_invalid_total 0" in body
    # ...step-collapse families (ISSUE 12, swarmturbo): UNet evals by
    # mode, DeepCache-skipped steps, and the per-image full-eval
    # histogram — label vocabularies pre-seeded, series process-
    # cumulative (other suites may have stepped lanes already, so
    # assert presence, not zero, for the mode-labeled counter)...
    from chiaswarm_tpu.obs.metrics import STEPPER_UNET_EVAL_MODES

    assert "# TYPE chiaswarm_stepper_unet_evals_total counter" in body
    for mode in STEPPER_UNET_EVAL_MODES:
        assert (f'chiaswarm_stepper_unet_evals_total{{mode="{mode}"}}'
                in body), mode
    assert "# TYPE chiaswarm_stepper_steps_skipped_total counter" in body
    assert "chiaswarm_stepper_steps_skipped_total" in body
    assert ("# TYPE chiaswarm_stepper_unet_evals_per_image histogram"
            in body)
    assert "guard" in health and health["guard"]["enabled"] is True
    assert health["guard"]["restart_requested"] is False
    assert "chips_in_service" in health
    # ...compile-cache + hive families from the process registry...
    assert "chiaswarm_compile_cache_misses_total" in body
    assert "# TYPE chiaswarm_compiles_total counter" in body
    assert 'chiaswarm_hive_requests_total{endpoint="results",result="ok"}' \
        in body
    # ...the trace-ring eviction counter (ISSUE 13 satellite): present
    # at zero from scrape one so a scraper can alert on span loss...
    assert "chiaswarm_trace_spans_evicted_total 0" in body
    # ...swarmdurable families (ISSUE 14): the dead-letter replay
    # counter split by moment (live = hive healed mid-run, startup =
    # the PR-2 worker-restart path) and the hive-session outage gauge —
    # vocabularies pre-seeded from scrape one, and the healthy run
    # above replayed nothing...
    from chiaswarm_tpu.obs.metrics import DEAD_LETTER_REPLAY_WHEN

    for when in DEAD_LETTER_REPLAY_WHEN:
        assert (f'chiaswarm_dead_letter_replayed_total{{when="{when}"}} 0'
                in body), when
    assert "chiaswarm_hive_session_state 0" in body
    assert "chiaswarm_hive_outages_total 0" in body
    assert "chiaswarm_leases_assumed_lost_total 0" in body
    assert health["hive_session"]["state"] == "online"
    assert health["hive_epoch"] is None  # journal-less reference hive
    # ...swarmfed families (ISSUE 17): the per-shard half of the
    # session signal — one series per configured shard (a plain
    # hive_uri is shard 0 of 1), zeroed from scrape one so a
    # dashboard can tell "shard outage" from "series missing"...
    assert "# TYPE chiaswarm_hive_shard_session_state gauge" in body
    assert 'chiaswarm_hive_shard_session_state{shard="0"} 0' in body
    # ...phase latency histograms fed by the finished traces
    assert 'chiaswarm_job_phase_seconds_bucket{phase="upload",le="+Inf"}' \
        in body

    # /debug/traces: Perfetto-loadable chrome events with worker phases
    names = {e["name"] for e in chrome["traceEvents"]}
    assert {"job", "poll", "execute", "upload"} <= names
    assert {t["root"]["name"] for t in tree["traces"]} == {"job"}
    assert len(worker.traces) == 2


def test_federation_front_metric_families_preseeded():
    """swarmfed (ISSUE 17): the federation front's scrape body carries
    the per-shard depth/epoch/leased gauges zeroed for EVERY shard and
    each shard's steal/forward counters pre-seeded — all before any
    job, poll, or steal, so fleet dashboards see the full shard
    vocabulary from scrape one."""
    from chiaswarm_tpu.node.federation import FederatedHive

    fed = FederatedHive(n_shards=3, lease_s=30.0)
    body = render_all([fed.metrics]
                      + [shard.metrics for shard in fed.shards])

    assert "# TYPE chiaswarm_hive_shard_depth gauge" in body
    assert "# TYPE chiaswarm_hive_shard_epoch gauge" in body
    assert "# TYPE chiaswarm_hive_shard_leased gauge" in body
    for index in range(3):
        assert f'chiaswarm_hive_shard_depth{{shard="{index}"}} 0' \
            in body, index
        assert f'chiaswarm_hive_shard_epoch{{shard="{index}"}} 0' \
            in body, index
        assert f'chiaswarm_hive_shard_leased{{shard="{index}"}} 0' \
            in body, index
    # each shard pre-seeds its steal counter with the self-pair and
    # its forwarded-upload counter at zero
    assert "# TYPE chiaswarm_hive_steals_total counter" in body
    for index in range(3):
        assert (f'chiaswarm_hive_steals_total{{from="{index}",'
                f'to="{index}"}} 0' in body), index
    assert "chiaswarm_hive_shard_forwarded_uploads_total 0" in body


def test_planner_metric_families_preseeded_at_import():
    """swarmplan (ISSUE 19): importing the planner module pre-seeds
    every ``chiaswarm_planner_*`` family on the GLOBAL registry — the
    two fleet-size gauges at zero and the decisions counter carrying
    the full direction x reason label vocabulary — so a dashboard
    scraping /metrics sees the complete planner surface before the
    first planning tick ever runs."""
    import chiaswarm_tpu.node.planner  # noqa: F401  (import = pre-seed)
    from chiaswarm_tpu.obs.metrics import (
        PLANNER_DIRECTIONS,
        PLANNER_REASONS,
        REGISTRY,
    )

    body = render_all([REGISTRY])
    assert "# TYPE chiaswarm_planner_target_workers gauge" in body
    assert "# TYPE chiaswarm_planner_actual_workers gauge" in body
    assert "# TYPE chiaswarm_planner_decisions_total counter" in body
    assert "# TYPE chiaswarm_planner_placement_moves_total counter" \
        in body
    assert "# TYPE chiaswarm_planner_worker_hours_total counter" in body
    assert "chiaswarm_planner_target_workers 0" in body
    assert "chiaswarm_planner_actual_workers 0" in body
    # attached planners bind per-hive registries, so the global series
    # stay zeroed — and the whole label vocabulary is present
    for direction in PLANNER_DIRECTIONS:
        for reason in PLANNER_REASONS:
            assert (f'chiaswarm_planner_decisions_total{{'
                    f'direction="{direction}",reason="{reason}"}} 0'
                    in body), (direction, reason)
    assert "chiaswarm_planner_placement_moves_total 0" in body
    assert "chiaswarm_planner_worker_hours_total 0" in body


def test_fleet_endpoint_schema_from_heartbeat_scrape():
    """ISSUE 13 satellite: a heartbeating worker's metric snapshot
    lands in ``GET /api/fleet`` with the schema the item-5 autoscaler
    reads — per-worker demand/supply/state plus the hive aggregate."""
    import time as _time

    import aiohttp

    from chiaswarm_tpu.node.chaos import ChaoticExecutor
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker

    class StubSlot:
        depth = 2
        data_width = 1

        def descriptor(self):
            return "stub"

    async def scenario():
        hive = MiniHive(lease_s=30.0, delay_s=0.01)
        uri = await hive.start()
        hive.submit({"id": "fleet-1", "model_name": "m/ok",
                     "prompt": "p", "workflow": "txt2img",
                     "content_type": "application/json"})
        worker = Worker(
            settings=Settings(
                hive_uri=uri, hive_token="t", worker_name="fleet-obs",
                install_signal_handlers=False, heartbeat_s=0.05,
                poll_busy_s=0.02, poll_idle_s=0.04,
                drain_timeout_s=5.0, result_drain_timeout_s=5.0),
            pool=[StubSlot()],
            registry=ModelRegistry(catalog=[], allow_random=True),
            executor=ChaoticExecutor())
        task = asyncio.create_task(worker.run())
        try:
            await hive.wait_for_results(1, timeout=30)
            deadline = _time.monotonic() + 30
            while "fleet-obs" not in hive.fleet and \
                    _time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            async with aiohttp.ClientSession() as session:
                async with session.get(f"{hive.uri}/api/fleet") as resp:
                    assert resp.status == 200
                    snap = await resp.json()
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)
            await hive.stop()
        return snap

    snap = asyncio.run(scenario())
    assert set(snap) == {"at_s", "workers", "aggregate"}
    entry = snap["workers"]["fleet-obs"]
    for key in ("queue_depth", "inflight_jobs", "jobs_done", "jobs_shed",
                "chips_in_service", "overload", "age_s", "live",
                "partitioned", "leased_jobs"):
        assert key in entry, key
    assert entry["live"] is True and entry["partitioned"] is False
    assert set(entry["overload"]) == {"state", "sheds_total",
                                      "service_ewma_s"}
    aggregate = snap["aggregate"]
    for key in ("workers_reporting", "workers_live", "chips_in_service",
                "arrival_rate_rows_s", "lane_occupancy_mean",
                "queue_depth", "inflight_jobs", "jobs_done", "jobs_shed",
                "workers_in_brownout", "observed_arrival_jobs_s",
                "pending_jobs", "leased_jobs", "completed_jobs",
                "abandoned_jobs"):
        assert key in aggregate, key
    assert aggregate["workers_reporting"] == 1
    assert aggregate["completed_jobs"] == 1
    json.dumps(snap)


# ---------------------------------------------------------------------------
# acceptance: end-to-end tiny txt2img, stepper off AND on
# ---------------------------------------------------------------------------


def _run_tiny_job_and_get_trace(stepper: bool, monkeypatch, seed: int):
    import sys

    sys.path.insert(0, "tests")
    from fake_hive import FakeHive

    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.worker import Worker

    # lanes are default-on (ISSUE 7): the off leg must opt OUT explicitly
    monkeypatch.setenv("CHIASWARM_STEPPER", "1" if stepper else "0")
    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])

    async def scenario():
        hive = FakeHive()
        uri_settings = None
        await hive.start()
        hive.jobs.append({
            "id": f"e2e-{'lane' if stepper else 'solo'}",
            "model_name": "tiny", "prompt": "an observable astronaut",
            "seed": seed, "num_inference_steps": 2, "guidance_scale": 7.5,
            "height": 64, "width": 64, "content_type": "image/png"})
        uri_settings = _endpoint_settings(hive.uri)
        worker = Worker(settings=uri_settings, registry=registry,
                        pool=pool)
        task = asyncio.create_task(worker.run())
        try:
            await hive.wait_for_results(1, timeout=300)
            for _ in range(100):
                if getattr(worker, "health_address", None):
                    break
                await asyncio.sleep(0.05)
            host, port = worker.health_address
            import aiohttp

            async with aiohttp.ClientSession() as session:
                async with session.get(
                        f"http://{host}:{port}/debug/traces") as resp:
                    chrome = await resp.json()
                async with session.get(
                        f"http://{host}:{port}/metrics") as resp:
                    metrics_body = await resp.text()
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=30)
            await hive.stop()
        return hive.results, worker, chrome, metrics_body

    results, worker, chrome, metrics_body = asyncio.run(scenario())
    assert len(results) == 1
    assert results[0]["pipeline_config"].get("error") is None, results
    traces = worker.traces.traces()
    assert len(traces) == 1
    return traces[0], chrome, metrics_body


@pytest.mark.parametrize("stepper", [False, True],
                         ids=["stepper-off", "stepper-on"])
def test_e2e_tiny_txt2img_trace_spans(stepper, monkeypatch):
    """ISSUE 4 acceptance: the finished job's trace contains
    poll/execute/encode/step/decode/upload spans with positive, nested
    durations, on BOTH execution paths, and /debug/traces serves them
    as Perfetto-loadable JSON next to a /metrics scrape that shows the
    compile-cache counters the run populated."""
    trace, chrome, metrics_body = _run_tiny_job_and_get_trace(
        stepper, monkeypatch, seed=41 if stepper else 40)

    root = trace.root
    phases = [c.name for c in root.children]
    assert phases == ["poll", "execute", "upload"]
    execute = root.children[1]
    for name in ("encode", "step", "decode"):
        node = execute.find(name)
        assert node is not None, f"missing {name} span in {phases}"
        assert node.duration_s > 0
        # nested INSIDE the execute phase's interval
        assert node.t0 >= execute.t0 - 1e-9
        assert node.t1 <= execute.t1 + 1e-9
    for child in root.children:
        assert child.duration_s > 0
    assert root.find("upload.http") is not None  # nests under upload
    assert trace.meta["outcome"] == "ok"
    assert trace.meta["settled"] == "uploaded"
    if stepper:
        # the lane run stamps its lane-side timeline into the step span
        assert "lane" in execute.find("step").meta

    # Perfetto export of the same tree via the live endpoint
    names = {e["name"] for e in chrome["traceEvents"]}
    assert {"job", "poll", "execute", "encode", "step", "decode",
            "upload"} <= names
    for event in chrome["traceEvents"]:
        assert event["ph"] == "X" and event["dur"] >= 1

    # the run compiled real executables; the registry saw them
    assert 'chiaswarm_compile_cache_misses_total{cache="executables"' \
        in metrics_body
    if stepper:
        assert "chiaswarm_stepper_steps_executed_total 2" in metrics_body
        assert "chiaswarm_stepper_step_seconds_count" in metrics_body
        # the per-lane occupancy histogram sampled at each lane step
        # (ISSUE 5 obs tie-in) rides the same scrape, labeled by the
        # lane's (bounded) width — never by unbounded lane id
        assert 'chiaswarm_stepper_lane_occupancy_ratio_bucket{width="' \
            in metrics_body
        # lease/resume families (ISSUE 6): present at zero on a healthy
        # run — they only move when the fleet machinery redelivers
        assert "chiaswarm_stepper_rows_resumed_total 0" in metrics_body
        assert "chiaswarm_stepper_resumes_rejected_total 0" in metrics_body
        assert "# TYPE chiaswarm_stepper_resume_step histogram" \
            in metrics_body
        assert "chiaswarm_checkpoints_written_total" in metrics_body


def test_lane_occupancy_histogram_semantics():
    """The per-lane occupancy family (obs/metrics.py): ratio buckets in
    eighths, one series per lane-width label (bounded — lane IDs would
    leak a series per retired lane), registered on the process-global
    registry exactly once (get-or-create)."""
    from chiaswarm_tpu.obs.metrics import (
        OCCUPANCY_BUCKETS, lane_occupancy_histogram)

    reg = Registry()
    hist = lane_occupancy_histogram(reg)
    assert lane_occupancy_histogram(reg) is hist  # idempotent
    assert hist.buckets == OCCUPANCY_BUCKETS

    # a 4-wide lane stepping at 1, 2, 4, 4 active rows
    for active in (1, 2, 4, 4):
        hist.observe(active / 4, width="4")
    hist.observe(0.5, width="16")  # wider lane family: its own series
    assert hist.count(width="4") == 4 and hist.count(width="16") == 1
    assert hist.sum(width="4") == pytest.approx(2.75)

    body = reg.render()
    assert ('chiaswarm_stepper_lane_occupancy_ratio_bucket'
            '{width="4",le="0.25"} 1') in body
    assert ('chiaswarm_stepper_lane_occupancy_ratio_bucket'
            '{width="4",le="1"} 4') in body
    assert ('chiaswarm_stepper_lane_occupancy_ratio_count{width="16"} 1'
            ) in body

    # the real sampler feeds the process-global registry
    global_hist = lane_occupancy_histogram()
    from chiaswarm_tpu.obs import metrics as obs_metrics

    assert obs_metrics.REGISTRY.get(
        "chiaswarm_stepper_lane_occupancy_ratio") is global_hist


# ---------------------------------------------------------------------------
# swarmlens (ISSUE 11): numerics ring + histogram percentiles
# ---------------------------------------------------------------------------


def test_numerics_ring_bounded_eviction_keeps_newest():
    """The flight-recorder ring is bounded: the oldest records evict,
    seq numbers stay monotonic, and the eviction counter tells the
    operator the window was exceeded."""
    from chiaswarm_tpu.obs.numerics import NumericsRing

    ring = NumericsRing(capacity=4)
    for i in range(10):
        ring.record("p", step=i, l2=float(i))
    records = ring.snapshot()
    assert len(records) == 4
    assert [r["step"] for r in records] == [6, 7, 8, 9]
    assert [r["seq"] for r in records] == [6, 7, 8, 9]
    stats = ring.stats()
    assert stats["total"] == 10 and stats["evicted"] == 6
    assert stats["depth"] == 4 and stats["capacity"] == 4

    # prefix filter + limit serve the /debug/numerics query params
    ring.record("other.probe", step=99)
    assert [r["probe"] for r in ring.snapshot(probe_prefix="other")] == \
        ["other.probe"]
    assert len(ring.snapshot(limit=2)) == 2

    # drain is snapshot+clear (the bisect driver's per-run capture)
    drained = ring.drain()
    assert len(drained) == 4 and len(ring) == 0


def test_numerics_ring_records_are_json_and_dumpable(tmp_path):
    from chiaswarm_tpu.obs import numerics

    ring = numerics.NumericsRing(capacity=8)
    ring.record("a.b", step=1, shard=2, l2=1.5, mean=0.5, absmax=2.0,
                nonfinite=0, checksum=123, size=64, note="job-1")
    path = tmp_path / "run.jsonl"
    n = numerics.dump(str(path), ring.snapshot())
    assert n == 1
    loaded = numerics.load_dump(str(path))
    assert loaded[0]["probe"] == "a.b" and loaded[0]["note"] == "job-1"


def test_histogram_percentile_interpolation():
    """Bucket-interpolated quantiles: the primitive behind the measured
    hang-budget suggestion."""
    from chiaswarm_tpu.obs.metrics import Histogram

    hist = Histogram("h", buckets=(1.0, 2.0, 4.0, 8.0))
    assert hist.percentile(0.5) is None  # empty series
    for v in (0.5, 1.5, 1.5, 3.0):
        hist.observe(v)
    # rank 2 of 4 lands in the (1, 2] bucket (2 obs): interpolated
    assert hist.percentile(0.5) == pytest.approx(1.5)
    assert hist.percentile(1.0) == pytest.approx(4.0)
    # overflow mass clamps to the last finite bound
    hist.observe(100.0)
    assert hist.percentile(0.99) == pytest.approx(8.0)
    pct = hist.percentiles((0.5, 0.99))
    assert set(pct) == {"p50", "p99"}

    labeled = Histogram("l", labelnames=("k",), buckets=(1.0, 2.0))
    labeled.observe(0.5, k="a")
    assert labeled.percentile(0.5, k="a") == pytest.approx(0.5)
    assert labeled.percentile(0.5, k="other") is None


def test_suggest_hang_budget_measured_vs_prior():
    """Below the sample floor the suggestion refuses to guess; above it
    the knobs derive from p50/p99 with documented clamps (ISSUE 11 —
    the PR-10 'priors, not measurements' carry-over closed)."""
    from chiaswarm_tpu.obs.metrics import Histogram
    from chiaswarm_tpu.serving.guard import suggest_hang_budget

    hist = Histogram("s", buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0))
    out = suggest_hang_budget(hist)
    assert out["measured"] is False and out["samples"] == 0
    assert out["current"]["factor"] == 20.0  # the documented prior

    for _ in range(60):
        hist.observe(0.04)
    for _ in range(4):
        hist.observe(0.4)  # a heavy tail: p99 lands past p50
    out = suggest_hang_budget(hist)
    assert out["measured"] is True and out["samples"] == 64
    s = out["suggested"]
    assert 4.0 <= s["factor"] <= 20.0
    assert s["floor_s"] >= 1.0
    assert s["ceil_s"] >= s["floor_s"]
    assert s["ceil_s"] <= out["current"]["ceil_s"]
    # measured floor tracks the tail, and sits far below the 30 s prior
    assert s["floor_s"] < out["current"]["floor_s"]
