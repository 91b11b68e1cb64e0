"""The job split part by part (ISSUE 26): one tiny-64 job through
MiniHive -> Worker.run() holds every named leaf in its span digest and the
leaves sum to the job; each reader added with them gives, on hand-made
records and a small hand-made trace, the number reckoned by hand, and None
where there is nothing to read."""

import json
import os
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import cell, readers, traffic  # noqa: E402
from perfbench.kinds import diffusion  # noqa: E402
from perfbench import spans as digests  # noqa: E402
from perfbench.attribution import phases_of  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = ["sdxl-1024.single", "sd15-512.single"]
NEW = ["lane_steps_s.lat", "lane_handoff_s.lat", "lane_wait_s.lat",
       "png_s.lat", "screen_s.lat", "job_unnamed_s.lat",
       "lane_host_ms.lat", "step_device_ms.lat"]
LEAVES = ["format", "schedule", "encode", "lane.wait", "lane.steps",
          "lane.handoff", "decode", "screen", "png", "safety"]
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}


# ---- the program: one window of tiny jobs, their records kept ----------


@pytest.fixture(scope="module")
def settled_jobs():
    """The window's settled jobs (hive flight records included) of one
    untraced tiny-64 run, taken where the run hands them to ``correct``."""
    config = json.loads(
        (ROOT / "perfbench" / "configs" / "tiny-64.json").read_text())
    workload = {"name": "sd15-512.single", "config": "tiny-64",
                "traffic": "single", "chips": 1}
    kept = {}
    real = diffusion.check

    def keeping(params, config, good, sent, **kw):
        kept["good"], kept["sent"] = good, sent
        return real(params, config, good, sent, **kw)

    patch = pytest.MonkeyPatch()
    patch.setenv("SWARM_TPU_ROOT", os.environ["SWARM_TPU_ROOT"])
    patch.setattr(diffusion, "check", keeping)
    try:
        result = cell.run_cell(
            workload=workload, config=config,
            mix=traffic.load_mix("single"), benchmark=BENCH,
            seed=2 ** 31 + 26, seconds=1.0, trace=False,
            t_start=time.monotonic(), require_tpu=False, out=sys.stderr)
    finally:
        patch.undo()
    assert result["failed"] == 0 and kept["good"]
    return kept["good"]


def test_the_digest_holds_every_named_leaf(settled_jobs):
    for settled in settled_jobs:
        digest = digests.final_digest(settled["record"])
        names = [span["name"] for span in digest["spans"]]
        for leaf in LEAVES + ["handover", "result.wait"]:
            assert leaf in names, (leaf, names)
        # the lane's three parts lie end to end inside the step span
        by_name = {span["name"]: span for span in digest["spans"]}
        wait, steps, handoff, step = (by_name[n] for n in (
            "lane.wait", "lane.steps", "lane.handoff", "step"))
        assert wait["t0_s"] + wait["dur_s"] == pytest.approx(
            steps["t0_s"], abs=2e-6)
        assert steps["t0_s"] + steps["dur_s"] == pytest.approx(
            handoff["t0_s"], abs=2e-6)
        assert handoff["t0_s"] + handoff["dur_s"] <= \
            step["t0_s"] + step["dur_s"] + 1e-6
        # the lane's stamps stay in the process: not in the digest's
        # metadata, not in the uploaded pipeline_config
        assert "_stamps" not in (by_name["step"].get("meta") or {})
        stepper = settled["result"]["pipeline_config"]["stepper"]
        assert "_stamps" not in stepper and "splice_wait_s" in stepper


def test_the_named_leaves_sum_to_the_job(settled_jobs):
    for settled in settled_jobs:
        record = settled["record"]
        digest = digests.final_digest(record)
        frozen = phases_of(record)
        total = float(record["settled"]["t"]) - float(record["submitted_at"])
        named = (frozen["hive_queue"] + frozen["upload"]
                 + digests.phase_seconds(digest, "poll")
                 + digests.span_seconds(digest, LEAVES))
        assert 0.90 * total <= named <= 1.005 * total, (named, total, digest)
        # the three lane parts are the frozen attribution's steps +
        # lane_wait, cut differently
        lane = digests.span_seconds(
            digest, ["lane.wait", "lane.steps", "lane.handoff"])
        assert lane <= frozen["steps"] + frozen["lane_wait"] + 1e-3
        assert lane >= 0.9 * (frozen["steps"] + frozen["lane_wait"])


# ---- the readers, on records and a trace made by hand --------------------


def spans_of(**seconds):
    """Digest spans laid end to end from t0_s = 0.02 (phase execute)."""
    out, at = [], 0.02
    for name, dur in seconds.items():
        name = name.replace("_", ".").rstrip("2")
        out.append({"name": name, "phase": "execute", "t0_s": round(at, 6),
                    "dur_s": dur})
        if not name.startswith("lane."):
            at += dur
    return out


def record(job, *, queue, poll, upload, unnamed, spans):
    """A settled flight record on the hive's clock: submitted at 100 s,
    granted after ``queue``; the worker's digest runs ``poll`` + the
    leaf spans + ``unnamed``; the hive settles ``upload`` later."""
    leaves = sum(s["dur_s"] for s in spans
                 if s["name"] not in ("step", "lane.encode"))
    duration = poll + leaves + unnamed
    grant = 100.0 + queue
    return {
        "job_id": job, "submitted_at": 100.0,
        "events": [{"event": "submit", "t": 100.0},
                   {"event": "grant", "t": grant, "attempt": 1}],
        "attempts": [{"attempt": 1, "t": grant, "worker": "w", "digest": {
            "attempt": 1, "duration_s": duration,
            "phases": [{"name": "poll", "t0_s": 0.0, "dur_s": poll},
                       {"name": "execute", "t0_s": poll,
                        "dur_s": duration - poll},
                       {"name": "upload", "t0_s": duration, "dur_s": 0.0}],
            "spans": spans}}],
        "settled": {"t": grant + duration + upload, "attempt": 1,
                    "outcome": "ok"}}


def lane_job(job, *, steps, handoff, png, unnamed):
    """One job of the changed program: step = lane.wait + lane.steps +
    lane.handoff; two png spans (add_images, get_results)."""
    spans = spans_of(
        handover=0.001, format=0.002, schedule=0.01, encode=0.004,
        lane_encode=0.0039,
        step=0.03 + steps + handoff, lane_wait=0.03, lane_steps=steps,
        lane_handoff=handoff, decode=0.001, screen=0.002, png=0.001,
        safety=0.0005, png2=png - 0.001)
    next(s for s in spans if s["name"] == "step")["meta"] = {
        "splice_wait_s": 0.03, "lane": 1}
    spans.append({"name": "result.wait", "phase": "execute", "t0_s": 9.0,
                  "dur_s": 0.0})
    return record(job, queue=0.02, poll=0.003, upload=0.007,
                  unnamed=unnamed, spans=spans)


def parent_job(job):
    """The parent's digest: one opaque step span, nothing after it."""
    spans = spans_of(format=0.002, encode=0.004, step=1.3, decode=0.001)
    spans[2]["meta"] = {"splice_wait_s": 0.03}
    return record(job, queue=0.02, poll=0.003, upload=0.007, unnamed=0.045,
                  spans=spans)


def histogram(values):
    return {"type": "histogram", "values": {
        key: {"counts": [], "sum": total, "count": count}
        for key, (total, count) in values.items()}}


def context(records, *, device=TPU, before=None, after=None, traced=None):
    good = [{"id": r["job_id"], "record": r} for r in records]
    ran = {"before": {"registry": before or {}, "stepper": {}},
           "after": {"registry": after or {}, "stepper": {}},
           "traced": traced, "sent": {}}
    return readers.Context(
        workload={"name": "sd15-512.single"}, config={}, mix={}, ran=ran,
        good=good, latencies=[], window_s=51.0, device=device, capture=None)


JOBS = [lane_job("a", steps=1.10, handoff=0.10, png=0.020, unnamed=0.010),
        lane_job("b", steps=1.20, handoff=0.12, png=0.030, unnamed=0.030),
        lane_job("c", steps=1.16, handoff=0.11, png=0.024, unnamed=0.020)]
BEFORE = {"chiaswarm_stepper_boundary_seconds": histogram({
              "admit": (1.0, 10), "drain": (5.0, 10), "retire": (1.0, 10),
              "checkpoint": (1.0, 10), "handoff": (1.0, 10),
              "idle": (9.0, 3)}),
          "chiaswarm_stepper_step_seconds": histogram({"": (3.0, 100)})}
AFTER = {"chiaswarm_stepper_boundary_seconds": histogram({
             "admit": (1.1, 20), "drain": (9.0, 20), "retire": (1.2, 20),
             "checkpoint": (1.7, 20), "handoff": (1.5, 20),
             "idle": (30.0, 9)}),
         "chiaswarm_stepper_step_seconds": histogram({"": (6.0, 200)})}
BY_HAND = {
    "lane_steps_s.lat": 1.16, "lane_handoff_s.lat": 0.11,
    "lane_wait_s.lat": 0.03, "png_s.lat": 0.024,
    "screen_s.lat": 0.0025,
    # what the record was built with, and the 1 ms hand-over to the
    # executor thread, which is named but not among the metric's leaves
    "job_unnamed_s.lat": 0.021,
    # (0.1 + 0.2 + 0.7 + 0.5) s over 100 steps; drain and idle left out
    "lane_host_ms.lat": 15.0,
    # the fixture's five runs: 41, 41, 42, 40, 41 ms
    "step_device_ms.lat": 41.0,
}


@pytest.fixture
def traced(monkeypatch):
    """A traced run whose xplane is the fixture's plain form."""
    from perfbench import programs

    form = json.loads((ROOT / "perfbench" / "fixtures"
                       / "trace_programs.json").read_text())
    monkeypatch.setattr(programs, "load", lambda directory, window_s: form)
    return {"dir": "unused", "window_s": form["window_s"]}


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_number_reckoned_by_hand(name, traced):
    ctx = context(JOBS, before=BEFORE, after=AFTER, traced=traced)
    assert readers.read(name, ctx) == pytest.approx(BY_HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_on_a_program_without_the_span(name, monkeypatch):
    """The parent: one opaque step span, no boundary family, every
    program called jit_fn. None, never 0, and no exception."""
    from perfbench import programs

    parent_trace = {"window_s": 1.0, "modules": [
        ["jit_fn(4316745985256595699)", 0, 41_000_000]]}
    monkeypatch.setattr(programs, "load", lambda d, w: parent_trace)
    steps_only = {"chiaswarm_stepper_step_seconds": histogram(
        {"": (3.0, 100)})}
    ctx = context([parent_job("p1"), parent_job("p2")], before={},
                  after=steps_only, traced={"dir": "x", "window_s": 1.0})
    assert readers.read(name, ctx) is None
    # no settled job, no trace at all
    assert readers.read(name, context([])) is None
    # and off the chip the readers added with ISSUE 26 stay silent
    ctx = context(JOBS, device=CPU, before=BEFORE, after=AFTER,
                  traced={"dir": "x", "window_s": 1.0})
    assert readers.read(name, ctx) is None


def test_a_trace_that_is_not_there_reads_none(tmp_path):
    ctx = context(JOBS, traced={"dir": str(tmp_path), "window_s": 5.0})
    assert readers.read("step_device_ms.lat", ctx) is None


def test_programs_are_told_apart_by_module_name():
    from perfbench import programs

    form = json.loads((ROOT / "perfbench" / "fixtures"
                       / "trace_programs.json").read_text())
    totals = programs.totals(form)
    assert set(totals) == {"jit_stepper_step", "jit_stepper_decode",
                           "jit_stepper_encode", "jit_stepper_init",
                           "jit_convert_element_type"}
    assert totals["jit_stepper_step"]["count"] == 5
    assert totals["jit_stepper_decode"]["seconds"] == pytest.approx(0.096)
    assert programs.mean_ms(form, "jit_stepper_decode") \
        == pytest.approx(96.0)
    assert programs.mean_ms(form, "jit_fn") is None
    assert programs.program_name("jit_stepper_step(43167)") \
        == "jit_stepper_step"


def test_unnamed_is_what_the_leaves_leave_over():
    """Against the frozen attribution on the same record: its ``other``
    holds the finish path; job_unnamed takes the named leaves out."""
    job = JOBS[1]
    frozen = phases_of(job)
    assert frozen["steps"] == pytest.approx(1.20 + 0.12)
    assert frozen["lane_wait"] == pytest.approx(0.03)
    # handover, schedule, screen, png, safety and the unnamed rest
    assert frozen["other"] == pytest.approx(
        0.001 + 0.01 + 0.002 + 0.030 + 0.0005 + 0.030)
    digest = digests.final_digest(job)
    assert digests.span_seconds(digest, ["lane.wait", "lane.steps",
                                         "lane.handoff"]) \
        == pytest.approx(frozen["steps"] + frozen["lane_wait"])
    assert digests.span_seconds(digest, ["no.such.span"]) is None
    assert digests.final_digest({"settled": None}) is None


# ---- the files -----------------------------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_listed_by_both_cells_and_names_its_source(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == CELLS and entry["moves"] == "job_p50_s"
    assert entry["better"] == "lower"
    spec = json.loads((ROOT / "perfbench" / "metrics"
                       / f"{name}.json").read_text())
    reader = ROOT / "perfbench" / "readers" / f"{spec['reader']}.py"
    assert reader.exists() and "def read(context" in reader.read_text()
    # the ``what`` names the span, counter or program it reads
    for source in (spec["args"].get("spans") or spec["args"].get("leaves")
                   or ([spec["args"]["program"]]
                       if "program" in spec["args"]
                       else ["chiaswarm_stepper_boundary_seconds"]
                       + spec["args"]["parts"])):
        assert source in spec["what"], (name, source)
    # the accepted entries come first, untouched, the new ones after
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == NEW
