"""swarmsight suite (ISSUE 13): cross-worker flight records.

Four layers:

- **Recorder units** (fake clock, no workers): trace-context stamping at
  grant, span-digest capture at settle, the hive-clock event timeline,
  deadline-budget attribution arithmetic, verify() anomaly detection,
  and the bounded store.
- **Timeline stitching through MiniHive** (fake clock): shed -> requeue
  -> complete and late-upload salvage each yield exactly ONE flight
  record with the full attempt chain.
- **Real-worker wire contract** (ChaoticExecutor, no pipelines): a
  context-carrying job uploads a span digest the hive pops into the
  record; with NO hive trace context (reference-hive parity) the upload
  payload keeps today's exact key set and the trace still carries the
  ``queued_s``/``attempt`` root attributes.
- **THE acceptance gate** (slow tier; real lanes): a 3-worker fleet
  with one scripted mid-lane kill yields a single stitched record for
  the killed job spanning both workers — grant(1, A) -> checkpoints ->
  redelivery -> grant(2, B) with resume_step >= 1 -> exactly-once
  settle — and tools/job_flight.py renders it.
"""

from __future__ import annotations

import asyncio
import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chiaswarm_tpu.node.chaos import ChaoticExecutor, ChaoticHive
from chiaswarm_tpu.node.executor import error_result
from chiaswarm_tpu.node.minihive import MiniHive
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.worker import Worker
from chiaswarm_tpu.obs import flight as obs_flight
from chiaswarm_tpu.obs import trace as obs_trace
from chiaswarm_tpu.obs.flight import (
    ATTRIBUTION_PHASES,
    SPAN_DIGEST_KEY,
    TRACE_CTX_KEY,
    FlightRecorder,
    budget_attribution,
    flight_to_chrome,
    render_timeline,
    render_tree,
    span_digest,
)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _tmp_root(tmp_path, monkeypatch):
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path))
    return tmp_path


def _job(job_id: str, chaos=None, model: str = "shared/tiny", **over):
    job = {"id": job_id, "model_name": model, "prompt": f"p {job_id}",
           "num_inference_steps": 2, "height": 64, "width": 64,
           "workflow": "txt2img", "deadline_s": 2.0,
           "content_type": "application/json"}
    if chaos is not None:
        job["chaos"] = chaos
    job.update(over)
    return job


def _ok_result(job_id: str, worker: str = "", digest=None) -> dict:
    result = {"id": job_id, "artifacts": {}, "nsfw": False,
              "pipeline_config": {"mode": "test"}}
    if worker:
        result["worker_name"] = worker
    if digest is not None:
        result[SPAN_DIGEST_KEY] = digest
    return result


def _digest(attempt: int, worker: str, *, duration_s: float = 0.5,
            splice_wait_s: float = 0.0) -> dict:
    """Hand-built digest shaped exactly like obs_flight.span_digest's
    output (the units below prove the real builder matches)."""
    return {
        "trace_id": "t" * 16, "span_id": f"{'t' * 16}.{attempt}",
        "attempt": attempt, "worker": worker,
        "started_at_unix": 1_700_000_000.0,
        "duration_s": duration_s,
        "phases": [
            {"name": "poll", "t0_s": 0.0, "dur_s": 0.05},
            {"name": "execute", "t0_s": 0.05,
             "dur_s": duration_s - 0.05},
        ],
        "spans": [
            {"name": "format", "phase": "execute", "t0_s": 0.05,
             "dur_s": 0.01},
            {"name": "encode", "phase": "execute", "t0_s": 0.06,
             "dur_s": 0.04},
            {"name": "step", "phase": "execute", "t0_s": 0.1,
             "dur_s": 0.3,
             "meta": {"splice_wait_s": splice_wait_s, "resume_step": 0}},
            {"name": "decode", "phase": "execute", "t0_s": 0.4,
             "dur_s": 0.05},
        ],
    }


# ---------------------------------------------------------------------------
# recorder units (fake clock)
# ---------------------------------------------------------------------------


def test_grant_stamps_trace_context_and_settle_builds_attribution():
    clock = [0.0]
    hive = MiniHive(lease_s=30.0, clock=lambda: clock[0])
    hive.submit(_job("f1"))

    [payload] = hive._take_jobs("wA")
    ctx = payload[TRACE_CTX_KEY]
    assert ctx["attempt"] == 1
    assert ctx["span_id"] == f"{ctx['trace_id']}.1"

    clock[0] = 1.0
    ack = hive._record_result(
        _ok_result("f1", "wA", digest=_digest(1, "wA")), "wA")
    assert ack == {"status": "ok"}
    # the digest was popped OFF the stored envelope into the record
    assert SPAN_DIGEST_KEY not in hive.completed["f1"]

    record = hive.flights.get("f1")
    assert record["model"] == "shared/tiny"
    assert record["workflow"] == "txt2img"
    assert record["deadline_s"] == 2.0
    assert [e["event"] for e in record["events"]] == \
        ["submit", "grant", "settled"]
    [attempt] = record["attempts"]
    assert attempt["attempt"] == 1 and attempt["worker"] == "wA"
    assert attempt["digest"]["worker"] == "wA"

    attribution = record["attribution"]
    assert attribution["measured"] is True
    assert set(attribution["phases"]) == set(ATTRIBUTION_PHASES)
    # grant at t=0, settle at t=1.0, digest covers 0.5s of worker time:
    # the upload leg is the hive-anchored remainder
    assert attribution["phases"]["upload"] == pytest.approx(0.5)
    assert attribution["phases"]["admission"] == pytest.approx(0.1)
    assert attribution["phases"]["steps"] == pytest.approx(0.3)
    assert attribution["phases"]["decode"] == pytest.approx(0.05)
    assert attribution["total_s"] == pytest.approx(1.0)
    assert hive.flights.verify(["f1"]) == []

    # the lane splice wait splits out of the step span
    hive.submit(_job("f2"))
    hive._take_jobs("wA")
    clock[0] = 2.0
    hive._record_result(
        _ok_result("f2", "wA",
                   digest=_digest(1, "wA", splice_wait_s=0.2)), "wA")
    phases = hive.flights.get("f2")["attribution"]["phases"]
    assert phases["lane_wait"] == pytest.approx(0.2)
    assert phases["steps"] == pytest.approx(0.1)

    # a garbage digest "attempt" from the wire must degrade to the
    # lease books (digest dropped, not filed as an orphan), never crash
    # an already-counted settle into a permanently unsettled record
    hive.submit(_job("f3"))
    hive._take_jobs("wA")
    clock[0] = 3.0
    bad = _ok_result("f3", "wA",
                     digest={"attempt": "x", "worker": "wA"})
    assert hive._record_result(bad, "wA") == {"status": "ok"}
    record = hive.flights.get("f3")
    assert record["settled"]["attempt"] == 1
    assert all(a["digest"] is None for a in record["attempts"])
    assert hive.flights.verify(["f3"]) == []


def test_flight_endpoints_serve_record_and_404():
    async def scenario():
        import aiohttp

        clock = [0.0]
        hive = MiniHive(lease_s=30.0, clock=lambda: clock[0])
        await hive.start()
        try:
            hive.submit(_job("e1"))
            hive._take_jobs("wA")
            clock[0] = 0.4
            hive._record_result(
                _ok_result("e1", "wA", digest=_digest(1, "wA")), "wA")
            async with aiohttp.ClientSession() as session:
                async with session.get(
                        f"{hive.uri}/api/flight/e1") as resp:
                    assert resp.status == 200
                    record = await resp.json()
                async with session.get(
                        f"{hive.uri}/api/flight/ghost") as resp:
                    assert resp.status == 404
                    missing = await resp.json()
                async with session.get(
                        f"{hive.uri}/api/flight") as resp:
                    assert resp.status == 200
                    index = await resp.json()
        finally:
            await hive.stop()
        return record, missing, index

    record, missing, index = asyncio.run(scenario())
    assert record["job_id"] == "e1"
    assert record["settled"]["outcome"] == "ok"
    assert record["attribution"]["measured"] is True
    assert missing["status"] == "unknown"
    assert index["jobs"] == ["e1"] and index["settled"] == 1


def test_shed_requeue_complete_yields_one_record_with_attempt_chain():
    clock = [0.0]
    hive = MiniHive(lease_s=30.0, clock=lambda: clock[0])
    hive.submit(_job("s1"))

    [first] = hive._take_jobs("wA")
    clock[0] = 0.5
    shed = error_result(_job("s1"), "shed by overload control",
                        kind="overloaded")
    shed[SPAN_DIGEST_KEY] = _digest(1, "wA", duration_s=0.1)
    assert hive._record_result(shed, "wA")["status"] == "requeued"

    clock[0] = 1.0
    [second] = hive._take_jobs("wB")
    assert second[TRACE_CTX_KEY]["attempt"] == 2
    assert second[TRACE_CTX_KEY]["trace_id"] == \
        first[TRACE_CTX_KEY]["trace_id"]

    clock[0] = 2.0
    hive._record_result(_ok_result("s1", "wB", digest=_digest(2, "wB")),
                        "wB")

    record = hive.flights.get("s1")
    events = [e["event"] for e in record["events"]]
    assert events == ["submit", "grant", "redispatched", "grant",
                      "settled"]
    assert [a["attempt"] for a in record["attempts"]] == [1, 2]
    # BOTH attempts' digests are part of the story — the shed one too
    assert [a["digest"]["worker"] for a in record["attempts"]] == \
        ["wA", "wB"]
    assert record["settled"] == {"t": 2.0, "worker": "wB",
                                 "outcome": "ok", "attempt": 2}
    # the failed attempt's wall time books as retry overhead
    assert record["attribution"]["phases"]["retry"] == pytest.approx(0.5)
    assert hive.flights.verify(["s1"]) == []


def test_late_upload_salvage_completes_the_record():
    clock = [0.0]
    hive = MiniHive(lease_s=1.0, max_attempts=2, clock=lambda: clock[0])
    hive.submit(_job("z1"))
    for worker in ("wA", "wB"):
        hive._take_jobs(worker)
        clock[0] += 2.0
        hive.sweep()
    assert hive.abandoned == ["z1"]

    # the straggler upload lands anyway: salvage settles the record
    clock[0] += 1.0
    ack = hive._record_result(
        _ok_result("z1", "wB", digest=_digest(2, "wB")), "wB")
    assert ack == {"status": "ok"}
    record = hive.flights.get("z1")
    events = [e["event"] for e in record["events"]]
    assert "abandoned" in events and "salvaged" in events
    assert events.count("settled") == 1
    assert events.count("lease_expired") == 2
    assert record["settled"]["attempt"] == 2
    assert hive.flights.verify(["z1"]) == []
    # attribution must NOT double-count the salvaged attempt: attempt 1
    # (grant t=0 -> expiry t=2) is retry; attempt 2's grant-to-expiry
    # wall is the productive work its own digest attributes, so only
    # 2.0s books as retry, not 4.0
    attribution = record["attribution"]
    assert attribution["phases"]["retry"] == pytest.approx(2.0)
    total = attribution["total_s"]
    assert sum(attribution["phases"].values()) == pytest.approx(
        total, rel=0.01)

    # duplicate after settle: recorded, never re-settled
    hive._record_result(_ok_result("z1", "wA"), "wA")
    record = hive.flights.get("z1")
    assert [e["event"] for e in record["events"]].count("settled") == 1
    assert "duplicate_upload" in [e["event"] for e in record["events"]]


def test_verify_flags_missing_gaps_orphans_and_unsettled():
    recorder = FlightRecorder(capacity=8)
    recorder.open("v1", _job("v1"), t=0.0)
    recorder.grant("v1", attempt=1, worker="wA", t=0.1)
    assert recorder.verify(["v1"], require_settled=False) == []
    assert recorder.verify(["v1"]) == ["v1: never settled"]
    assert recorder.verify(["ghost"], require_settled=False) == \
        ["ghost: no flight record"]

    # attempt gap: grant 3 without 2
    recorder.grant("v1", attempt=3, worker="wB", t=0.2)
    problems = recorder.verify(["v1"], require_settled=False)
    assert any("attempt gap" in p for p in problems)

    # orphan digest: an attempt never granted
    recorder.open("v2", _job("v2"), t=0.0)
    recorder.grant("v2", attempt=1, worker="wA", t=0.1)
    recorder.add_digest("v2", _digest(7, "wX"))
    problems = recorder.verify(["v2"], require_settled=False)
    assert any("orphan span digest" in p for p in problems)

    # bounded store: eviction is counted
    small = FlightRecorder(capacity=2)
    for i in range(4):
        small.open(f"b{i}", _job(f"b{i}"), t=float(i))
    assert len(small) == 2 and small.evicted == 2
    assert small.snapshot()["evicted"] == 2


def test_span_digest_matches_real_trace_shape():
    trace = obs_trace.JobTrace(
        "job", id="d1", worker="wZ", attempt=2, trace_id="abc",
        span_id="abc.2", queued_s=0.25, resume_step=3)
    trace.phase("poll")
    trace.phase("execute")
    with trace.active():
        with obs_trace.span("format"):
            pass
        with obs_trace.span("encode"):
            pass
        with obs_trace.span("step", steps=2) as step:
            time.sleep(0.01)
            step.meta["splice_wait_s"] = 0.004
        with obs_trace.span("decode"):
            pass
    trace.phase("upload")
    digest = span_digest(trace, worker_name="wZ")
    assert digest["trace_id"] == "abc" and digest["span_id"] == "abc.2"
    assert digest["attempt"] == 2 and digest["worker"] == "wZ"
    assert digest["queued_s"] == 0.25 and digest["resume_step"] == 3.0
    assert [p["name"] for p in digest["phases"]] == \
        ["poll", "execute", "upload"]
    names = [s["name"] for s in digest["spans"]]
    assert names == ["format", "encode", "step", "decode"]
    step_entry = digest["spans"][2]
    assert step_entry["phase"] == "execute"
    assert step_entry["meta"]["splice_wait_s"] == 0.004
    assert step_entry["dur_s"] > 0
    json.dumps(digest)  # wire-safe

    # feed it through attribution end to end
    recorder = FlightRecorder(capacity=4)
    recorder.open("d1", _job("d1"), t=0.0)
    recorder.grant("d1", attempt=2, worker="wZ", t=0.1)
    recorder.add_digest("d1", digest)
    recorder.settle("d1", t=1.0, worker="wZ", outcome="ok", attempt=2)
    attribution = recorder.get("d1")["attribution"]
    assert attribution["phases"]["lane_wait"] == pytest.approx(
        0.004, abs=1e-6)
    assert attribution["phases"]["steps"] > 0


def test_attribution_without_digest_degrades_to_hive_phases():
    recorder = FlightRecorder(capacity=4)
    recorder.open("h1", _job("h1"), t=0.0)
    recorder.grant("h1", attempt=1, worker="wA", t=0.5)
    recorder.settle("h1", t=2.0, worker="wA", outcome="ok", attempt=1)
    attribution = recorder.get("h1")["attribution"]
    assert attribution["measured"] is False
    assert attribution["phases"]["hive_queue"] == pytest.approx(0.5)
    # the worker-side seconds are unattributable without a digest
    assert attribution["phases"]["other"] == pytest.approx(1.5)
    assert budget_attribution({"settled": None}) is None


# ---------------------------------------------------------------------------
# renderers + the CLI
# ---------------------------------------------------------------------------


def _settled_record() -> dict:
    clock = [0.0]
    hive = MiniHive(lease_s=30.0, clock=lambda: clock[0])
    hive.submit(_job("r1"))
    hive._take_jobs("wA")
    clock[0] = 0.5
    shed = error_result(_job("r1"), "shed", kind="overloaded")
    shed[SPAN_DIGEST_KEY] = _digest(1, "wA", duration_s=0.1)
    hive._record_result(shed, "wA")
    clock[0] = 1.0
    hive._take_jobs("wB")
    clock[0] = 2.0
    hive._record_result(
        _ok_result("r1", "wB", digest=_digest(2, "wB")), "wB")
    return hive.flights.get("r1")


def test_renderers_stitch_attempts_across_workers():
    record = _settled_record()
    tree = render_tree(record)
    assert "attempt 1 on wA" in tree and "attempt 2 on wB" in tree
    assert "redispatched" in tree and "budget attribution" in tree
    assert "clock_skew_s" in tree

    timeline = render_timeline(record)
    assert "[wA#1]" in timeline and "[wB#2]" in timeline
    assert "[hive] settled" in timeline

    chrome = flight_to_chrome(record)
    events = chrome["traceEvents"]
    # pid 0 = hive instants; one pid per worker; tid = attempt
    pids = {e["pid"] for e in events}
    assert {0, 1, 2} <= pids
    assert any(e["ph"] == "i" and e["name"] == "grant" for e in events)
    worker_names = {e["args"]["name"] for e in events
                    if e.get("name") == "process_name"}
    assert {"hive", "worker wA", "worker wB"} <= worker_names
    span_events = [e for e in events if e["ph"] == "X"]
    assert all(e["dur"] >= 1 for e in span_events)
    json.dumps(chrome)


def test_job_flight_cli_renders_from_file(tmp_path):
    record = _settled_record()
    path = tmp_path / "flight.json"
    path.write_text(json.dumps(record))
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "job_flight.py"),
         "--file", str(path)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "attempt 2 on wB" in out.stdout
    perfetto = subprocess.run(
        [sys.executable, str(REPO / "tools" / "job_flight.py"),
         "--file", str(path), "--format", "perfetto"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert perfetto.returncode == 0, perfetto.stderr
    doc = json.loads(perfetto.stdout)
    assert doc["traceEvents"]


# ---------------------------------------------------------------------------
# real-worker wire contract (ChaoticExecutor — no pipelines)
# ---------------------------------------------------------------------------


class StubSlot:
    depth = 2
    data_width = 1

    def descriptor(self):
        return "stub"


def _worker_settings(uri: str, name: str, **over):
    from chiaswarm_tpu.node.settings import Settings

    base = dict(
        hive_uri=uri, hive_token="t", worker_name=name,
        job_deadline_s=30.0, poll_busy_s=0.02, poll_idle_s=0.04,
        poll_backoff_base_s=0.02, poll_backoff_cap_s=0.1,
        upload_retries=3, upload_retry_delay_s=0.02,
        drain_timeout_s=5.0, result_drain_timeout_s=5.0,
        install_signal_handlers=False,
    )
    base.update(over)
    return Settings(**base)


def _run_worker_against(hive, jobs, **settings_over):
    async def scenario():
        uri = await hive.start()
        for job in jobs:
            hive.submit(job)
        worker = Worker(settings=_worker_settings(uri, "flight-w",
                                                  **settings_over),
                        pool=[StubSlot()],
                        registry=ModelRegistry(catalog=[],
                                               allow_random=True),
                        executor=ChaoticExecutor())
        task = asyncio.create_task(worker.run())
        try:
            await hive.wait_for_results(len(jobs), timeout=60)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)
            await hive.stop()
        return worker

    return asyncio.run(scenario())


def test_reference_hive_parity_no_context_no_digest():
    """With no hive trace context the upload payload is byte-compatible
    with today's: exactly the historical key set, no span digest — and
    the trace still stamps queued_s + attempt as root attributes
    (ISSUE 13 satellite)."""
    hive = ChaoticHive()
    worker = _run_worker_against(hive, [_job("p1")])
    [result] = hive.results
    assert set(result) == {"id", "artifacts", "nsfw", "worker_version",
                           "pipeline_config", "worker_name"}
    assert SPAN_DIGEST_KEY not in result
    [trace] = worker.traces.traces()
    assert trace.meta["attempt"] == 1
    assert trace.meta["queued_s"] == 0.0
    assert "trace_id" not in trace.meta


def test_minihive_job_uploads_digest_and_record_settles():
    """A context-carrying job's upload rides a real span digest; the
    hive pops it into the flight record (stored envelope unchanged) and
    the settled record attributes the budget."""
    hive = MiniHive(lease_s=30.0, delay_s=0.01)
    worker = _run_worker_against(hive, [_job("m1")])
    result = hive.completed["m1"]
    assert SPAN_DIGEST_KEY not in result
    assert set(result) == {"id", "artifacts", "nsfw", "worker_version",
                           "pipeline_config", "worker_name"}

    record = hive.flights.get("m1")
    [attempt] = record["attempts"]
    digest = attempt["digest"]
    assert digest["worker"] == "flight-w" and digest["attempt"] == 1
    assert [p["name"] for p in digest["phases"]] == \
        ["poll", "execute", "upload"]
    assert digest["trace_id"] == record["trace_id"]
    assert digest["span_id"] == f"{record['trace_id']}.1"
    assert record["settled"]["outcome"] == "ok"
    assert record["attribution"]["measured"] is True
    assert hive.flights.verify(["m1"]) == []
    # the worker-side trace JOINed the hive context
    [trace] = worker.traces.traces()
    assert trace.meta["trace_id"] == record["trace_id"]
    # queued_s rides the trace root on context-ful jobs too
    assert trace.meta["queued_s"] >= 0.0


def test_fleet_snapshot_from_real_heartbeats():
    """Heartbeats push per-worker metric snapshots; /api/fleet (and
    fleet_snapshot()) aggregates them — the item-5 data plane."""
    async def scenario():
        hive = MiniHive(lease_s=30.0, delay_s=0.01)
        uri = await hive.start()
        hive.submit(_job("hb1"))
        worker = Worker(settings=_worker_settings(uri, "flight-w",
                                                  heartbeat_s=0.05),
                        pool=[StubSlot()],
                        registry=ModelRegistry(catalog=[],
                                               allow_random=True),
                        executor=ChaoticExecutor())
        task = asyncio.create_task(worker.run())
        try:
            await hive.wait_for_results(1, timeout=60)
            # idle beats keep pushing metrics: wait for the first one
            deadline = time.monotonic() + 30
            while "flight-w" not in hive.fleet and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.02)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)
            await hive.stop()
        return hive

    hive = asyncio.run(scenario())
    snap = hive.fleet_snapshot()
    assert "flight-w" in snap["workers"]
    entry = snap["workers"]["flight-w"]
    for key in ("queue_depth", "inflight_jobs", "jobs_done",
                "chips_in_service", "overload"):
        assert key in entry, key
    aggregate = snap["aggregate"]
    assert aggregate["workers_reporting"] == 1
    assert aggregate["chips_in_service"] >= 1
    assert aggregate["completed_jobs"] == 1
    assert aggregate["observed_arrival_jobs_s"] >= 0.0

    # a DEAD worker's stale snapshot stays visible per-worker but must
    # not inflate the aggregate capacity an autoscaler provisions by
    hive.fleet["ghost"] = {"at": -1e9,
                           "metrics": {"chips_in_service": 50,
                                       "arrival_rate_rows_s": 99.0}}
    snap2 = hive.fleet_snapshot()
    assert snap2["workers"]["ghost"]["live"] is False
    assert snap2["aggregate"]["workers_reporting"] == 2
    assert snap2["aggregate"]["chips_in_service"] == \
        aggregate["chips_in_service"]
    assert snap2["aggregate"]["arrival_rate_rows_s"] < 99.0


# ---------------------------------------------------------------------------
# THE acceptance gate (slow tier; always runs in the CI Flight suite)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_flight_gate_kill_mid_lane_single_stitched_record(
        monkeypatch, tmp_path):
    """ISSUE 13 acceptance: 3 real-lane workers, one scripted mid-lane
    kill — the killed job's flight record stitches BOTH workers into
    one story (grant attempt 1 on the victim, checkpoint markers,
    redelivery, grant attempt 2 on a survivor whose digest records
    resume_step >= 1, exactly-once settle), and tools/job_flight.py
    renders it."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.08")

    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)

    def lane_job(i: int) -> dict:
        return {"id": f"fl-{i}", "model_name": "tiny",
                "prompt": f"flight prompt {i}", "seed": 700 + i,
                "num_inference_steps": 24, "guidance_scale": 7.5,
                "height": 64, "width": 64, "content_type": "image/png"}

    async def scenario():
        hive = MiniHive(lease_s=60.0, delay_s=0.01, max_jobs_per_poll=1)
        uri = await hive.start()
        for i in range(3):
            hive.submit(lane_job(i))
        workers = []
        for tag in ("a", "b", "c"):
            pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                            devices=jax.devices()[:1])
            workers.append(Worker(
                settings=_worker_settings(uri, f"flgate-{tag}",
                                          job_deadline_s=600.0,
                                          heartbeat_s=0.05),
                registry=registry, pool=pool))
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        victim = victim_job = None
        try:
            deadline = time.monotonic() + 240
            while victim is None and time.monotonic() < deadline:
                for job_id, ckpt in list(hive.checkpoints.items()):
                    holder = hive.lease_holder(job_id)
                    if ckpt.get("kind") == "lane" and \
                            int(ckpt.get("step", 0)) >= 1 and \
                            holder is not None:
                        victim_job, victim = job_id, holder
                        hive.partition(holder)
                        break
                if victim is None:
                    await asyncio.sleep(0.02)
            assert victim is not None, \
                f"no lane checkpoint ever reached the hive: {hive.stats()}"
            tasks[victim].cancel()
            await asyncio.gather(tasks[victim], return_exceptions=True)
            assert victim_job in hive.expire_worker(victim)
            await hive.wait_for_results(3, timeout=300)
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=60)
                                   for t in tasks.values()),
                                 return_exceptions=True)
            for worker in workers:
                for slot in worker.pool:
                    stepper = getattr(slot, "_stepper", None)
                    if stepper is not None:
                        stepper.shutdown()
            await hive.stop()
        return hive, victim, victim_job

    hive, victim, victim_job = asyncio.run(scenario())

    # exactly-once settle for every job, complete flight records all
    uploaded = hive.uploaded_ids()
    assert sorted(uploaded) == ["fl-0", "fl-1", "fl-2"]
    assert len(uploaded) == len(set(uploaded))
    assert hive.flights.verify(["fl-0", "fl-1", "fl-2"]) == []

    # ONE stitched record spans both workers with the full chain
    record = hive.flights.get(victim_job)
    events = [e["event"] for e in record["events"]]
    assert events.count("settled") == 1
    assert "checkpoint" in events
    assert "redelivered" in events or "lease_expired" in events
    grants = [e for e in record["events"] if e["event"] == "grant"]
    assert [g["attempt"] for g in grants][:2] == [1, 2]
    assert grants[0]["worker"] == victim
    survivor = record["settled"]["worker"]
    assert survivor != victim

    # the settling attempt's digest proves the mid-trajectory resume
    digests = {a["attempt"]: a["digest"]
               for a in record["attempts"] if a["digest"]}
    final = digests[record["settled"]["attempt"]]
    assert final["worker"] == survivor
    assert float(final.get("resume_step") or 0) >= 1
    step_spans = [s for s in final["spans"] if s["name"] == "step"]
    assert step_spans and all(s["dur_s"] > 0 for s in step_spans)
    assert record["attribution"]["phases"]["steps"] > 0

    # checkpoint markers on the timeline carry the victim's progress
    marks = [e for e in record["events"] if e["event"] == "checkpoint"]
    assert any(int(m.get("step") or 0) >= 1 for m in marks)

    # and the CLI renders the stitched record
    path = tmp_path / "gate-flight.json"
    path.write_text(json.dumps(record))
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "job_flight.py"),
         "--file", str(path), "--format", "timeline"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    assert f"[{survivor}#" in out.stdout
    assert "checkpoint" in out.stdout
