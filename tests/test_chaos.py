"""Chaos suite: deterministic fault injection against a REAL Worker.

Acceptance invariant (ISSUE 2): under a scripted schedule of fault modes
(dropped polls, hive 5xx, injected latency, non-JSON 400s, malformed
jobs, executor crashes, OOMs, transient fetch failures, hangs past the
deadline, upload failures), every injected job ends as exactly ONE
uploaded success-or-error envelope or ONE dead-letter file — no silent
drops — and the worker exits cleanly on stop.

Everything here is hermetic and deterministic: explicit fault scripts
(node/chaos.py), seeded jitter (node/resilience.py), no real pipelines
(the ChaoticExecutor replaces the executor seam), no network beyond
loopback.
"""

from __future__ import annotations

import asyncio
import json

import pytest

from chiaswarm_tpu.node.chaos import ChaoticExecutor, ChaoticHive
from chiaswarm_tpu.node.hive import BadWorkerError, HiveClient
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.resilience import (
    Backoff,
    BreakerBoard,
    DeadLetterSpool,
    backoff_delay,
    classify_exception,
    classify_result,
)
from chiaswarm_tpu.node.settings import Settings
from chiaswarm_tpu.node.worker import Worker


@pytest.fixture(autouse=True)
def _tmp_root(tmp_path, monkeypatch):
    """Isolate settings root (logs, dead-letter spool) per test."""
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path))
    return tmp_path


class StubSlot:
    """Executor-less slot: the ChaoticExecutor never touches the mesh.
    ``__call__`` mirrors the real slot contract (core/chip_pool.py) just
    enough for tests that drive the REAL executor's error paths —
    callbacks that raise before touching any device."""

    def __init__(self, depth: int = 2, data_width: int = 1,
                 name: str = "stub"):
        self.depth = depth
        self.data_width = data_width
        self.name = name

    def descriptor(self):
        return self.name

    def __call__(self, callback, **kwargs):
        model_name = kwargs.pop("model_name", None)
        seed = int(kwargs.pop("seed", None) or 0)
        artifacts, config = callback(self, model_name, seed=seed, **kwargs)
        config = dict(config)
        config["seed"] = seed
        return artifacts, config


def chaos_settings(uri: str = "http://unused", **over) -> Settings:
    base = dict(
        hive_uri=uri, hive_token="t", worker_name="chaos-worker",
        job_deadline_s=0.25,
        transient_retries=2,
        retry_backoff_s=0.01, retry_backoff_cap_s=0.05,
        breaker_threshold=2, breaker_cooldown_s=3600.0,
        poll_busy_s=0.02, poll_idle_s=0.05,
        poll_backoff_base_s=0.02, poll_backoff_cap_s=0.1,
        upload_retries=3, upload_retry_delay_s=0.01,
        drain_timeout_s=5.0, result_drain_timeout_s=5.0,
        install_signal_handlers=False,
    )
    base.update(over)
    return Settings(**base)


def _cjob(job_id: str, chaos=None, model: str | None = None, **over):
    job = {"id": job_id, "model_name": model or f"model/{job_id}",
           "prompt": f"p {job_id}", "num_inference_steps": 2,
           "height": 64, "width": 64, "content_type": "application/json"}
    if chaos is not None:
        job["chaos"] = chaos
    job.update(over)
    return job


def _worker(settings: Settings, executor: ChaoticExecutor,
            registry=None, hive=None, slots=None) -> Worker:
    return Worker(settings=settings,
                  pool=slots if slots is not None else [StubSlot()],
                  registry=registry if registry is not None else object(),
                  hive=hive if hive is not None else object(),
                  executor=executor)


# ---------------------------------------------------------------------------
# the acceptance scenario: scripted multi-mode fault schedule, zero loss
# ---------------------------------------------------------------------------


def test_chaos_zero_loss_e2e(tmp_path):
    """≥5 fault modes in one scripted run; every job accounted for as
    exactly one uploaded envelope or one dead-letter file; clean exit."""

    async def scenario():
        hive = ChaoticHive(
            # poll-side faults: dropped connection, server error, injected
            # latency, non-JSON misbehaving-worker 400, malformed job
            poll_faults=["drop", "ok", "http_500", "delay", "bad_worker",
                         "malformed"],
            # result-side faults, keyed by job id so upload order is moot
            result_faults={
                "c-retry": ["http_500", "ok"],
                "c-retry2": ["drop", "ok"],
                "c-dead": ["http_500"] * 10,  # exhausts every attempt
            },
            delay_s=0.02,
        )
        uri = await hive.start()
        jobs = [
            _cjob("c-ok"),
            _cjob("c-crash", chaos=["crash"]),       # executor raises
            _cjob("c-oom", chaos=["oom", "ok"]),     # ladder re-runs solo
            _cjob("c-fetch", chaos=["fetch", "ok"]),  # transient retry
            _cjob("c-hang", chaos=["hang"]),         # exceeds the deadline
            _cjob("c-fatal", chaos=["fatal"]),       # bad inputs
            _cjob("c-retry"),
            _cjob("c-retry2"),
            _cjob("c-dead"),
        ]
        for job in jobs:
            hive.submit(job)

        executor = ChaoticExecutor(hang_s=1.0)
        registry = ModelRegistry(catalog=[], allow_random=True)
        worker = Worker(settings=chaos_settings(uri), pool=[StubSlot()],
                        registry=registry, executor=executor)
        task = asyncio.create_task(worker.run())
        try:
            # all ids upload except c-dead (which must dead-letter);
            # malformed-1 is injected by the hive's own fault schedule
            await hive.wait_for_results(len(jobs) - 1 + 1, timeout=60)
            for _ in range(200):  # c-dead spools after its last retry
                if worker.dead_letters.depth() >= 1:
                    break
                await asyncio.sleep(0.05)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)  # clean exit
            await hive.stop()

        uploaded = hive.uploaded_ids()
        expected_upload = {j["id"] for j in jobs} - {"c-dead"}
        expected_upload.add("malformed-1")
        # exactly-once: no duplicates, no silent drops
        assert sorted(uploaded) == sorted(expected_upload)
        dead = list(worker.dead_letters.directory.glob("*.json"))
        assert len(dead) == 1
        assert json.loads(dead[0].read_text())["id"] == "c-dead"

        by_id = {r["id"]: r for r in hive.results}
        assert "error" not in by_id["c-ok"]["pipeline_config"]
        assert by_id["c-crash"]["pipeline_config"]["error_kind"] == "error"
        assert by_id["c-hang"]["pipeline_config"]["error_kind"] == "timeout"
        assert by_id["c-fatal"]["fatal_error"] is True
        # the ladder recovered these: final envelopes are successes
        for recovered in ("c-oom", "c-fetch"):
            assert "error" not in by_id[recovered]["pipeline_config"]
            assert executor.attempts[recovered] == 2

        # degradation-ladder observability (satellite: health counters)
        health = worker.health()
        assert health["jobs_timed_out"] >= 1
        assert health["jobs_retried"] >= 2
        assert health["jobs_failed"] >= 3
        assert health["upload_retries"] >= 3
        assert health["results_dead_lettered"] == 1
        assert health["dead_letter_depth"] == 1
        assert "breakers" in health
        # backoff reset on the first successful poll after the errors
        assert health["poll_consecutive_errors"] == 0

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# degradation ladder units (driven through the real Worker methods)
# ---------------------------------------------------------------------------


def test_oom_burst_splits_and_reruns_serially():
    """An OOM'd coalesced burst degrades to serial solo re-runs — the
    batched attempt happens once, then each member solo."""

    async def scenario():
        executor = ChaoticExecutor()
        worker = _worker(chaos_settings(), executor)
        jobs = [_cjob(f"b{i}", chaos=["oom", "ok"], model="shared/model")
                for i in range(3)]
        results = await worker._execute_burst(jobs, StubSlot())
        assert [classify_result(r) for r in results] == ["ok"] * 3
        assert executor.events[0] == ("batch", ["b0", "b1", "b2"])
        assert executor.events[1:] == [("solo", ["b0"]), ("solo", ["b1"]),
                                       ("solo", ["b2"])]
        assert worker.stats.jobs_retried == 3
        assert worker.stats.jobs_failed == 0  # all recovered

    asyncio.run(scenario())


def test_transient_fetch_failure_retries_with_backoff():
    async def scenario():
        executor = ChaoticExecutor()
        worker = _worker(chaos_settings(), executor)
        [result] = await worker._execute_burst(
            [_cjob("t1", chaos=["fetch", "fetch", "ok"])], StubSlot())
        assert classify_result(result) == "ok"
        assert executor.attempts["t1"] == 3  # 1 + transient_retries
        assert worker.stats.jobs_retried == 2

    asyncio.run(scenario())


def test_fatal_error_never_retried():
    async def scenario():
        executor = ChaoticExecutor()
        worker = _worker(chaos_settings(), executor)
        [result] = await worker._execute_burst(
            [_cjob("f1", chaos=["fatal", "ok"])], StubSlot())
        assert result["fatal_error"] is True
        assert executor.attempts["f1"] == 1
        assert worker.stats.jobs_failed == 1

    asyncio.run(scenario())


def test_deadline_uses_per_workflow_budget():
    """A hung job times out against ITS workflow's budget and reports an
    explicit timeout envelope (not a silent disappearance)."""

    async def scenario():
        executor = ChaoticExecutor(hang_s=30.0)
        settings = chaos_settings(
            job_deadline_s=100.0,  # generous default...
            workflow_deadline_s={"slowflow": 0.05})  # ...tight override
        worker = _worker(settings, executor)
        [result] = await worker._execute_burst(
            [_cjob("d1", chaos=["hang"], workflow="slowflow")], StubSlot())
        config = result["pipeline_config"]
        assert config["error_kind"] == "timeout"
        assert "deadline" in config["error"]
        assert "fatal_error" not in result  # the hive may retry elsewhere
        assert worker.stats.jobs_timed_out == 1

    asyncio.run(scenario())


def test_breaker_quarantines_model_then_probes_and_recovers():
    """K consecutive permanent failures quarantine the model in the
    registry (fast-refusal envelopes, no chip time); after the cooldown a
    half-open probe's success lifts the quarantine."""

    async def scenario():
        clock = [0.0]
        executor = ChaoticExecutor()
        registry = ModelRegistry(catalog=[], allow_random=True)
        worker = _worker(chaos_settings(), executor, registry=registry)
        worker.breakers = BreakerBoard(
            threshold=2, cooldown_s=10.0, clock=lambda: clock[0],
            on_open=registry.quarantine, on_close=registry.unquarantine,
            on_probe=registry.unquarantine)
        bad = "bad/checkpoint"

        for i in range(2):  # two consecutive execution crashes
            [result] = await worker._execute_burst(
                [_cjob(f"q{i}", chaos=["crash"], model=bad)], StubSlot())
            assert classify_result(result) == "error"
        assert registry.is_quarantined(bad)
        # satellite (ISSUE 8): quarantine surfaces through the ONE
        # authoritative per-model state enum /healthz serves
        assert registry.model_states()[bad] == "quarantined"
        assert worker.health()["models"][bad] == "quarantined"
        assert worker.health()["breakers"][bad]["state"] == "open"
        with pytest.raises(ValueError, match="quarantined"):
            registry.pipeline(bad)

        # while open: refused fast, executor never invoked
        [refused] = await worker._execute_burst(
            [_cjob("q2", chaos=["ok"], model=bad)], StubSlot())
        assert refused["pipeline_config"]["error_kind"] == "quarantined"
        assert "fatal_error" not in refused  # other nodes may serve it
        assert "q2" not in executor.attempts
        assert worker.stats.jobs_quarantined == 1

        clock[0] = 11.0  # past the cooldown: one half-open probe runs
        [probe] = await worker._execute_burst(
            [_cjob("q3", chaos=["ok"], model=bad)], StubSlot())
        assert classify_result(probe) == "ok"
        assert not registry.is_quarantined(bad)
        assert registry.model_states().get(bad) != "quarantined"
        assert worker.health()["breakers"][bad]["state"] == "closed"

    asyncio.run(scenario())


def test_half_open_admits_exactly_one_probe():
    """When the cooldown expires, a queued backlog must not stampede the
    likely-broken model: one probe at a time; its verdict decides."""
    clock = [0.0]
    board = BreakerBoard(threshold=1, cooldown_s=10.0,
                         clock=lambda: clock[0])
    board.record("m", ok=False)           # opens immediately (threshold 1)
    assert not board.allow("m")
    clock[0] = 11.0
    assert board.allow("m")               # the single half-open probe
    assert not board.allow("m")           # backlog stays gated
    assert not board.allow("m")
    board.record("m", ok=True)            # probe verdict: healthy
    assert board.allow("m") and board.allow("m")  # closed: all flow

    # failure verdict re-opens and re-arms the cooldown
    board.record("m", ok=False)
    assert not board.allow("m")           # 11.0 is the new open stamp
    clock[0] = 22.0
    assert board.allow("m")

    # an INCONCLUSIVE probe (bad user inputs) frees the slot for the
    # next probe instead of wedging the breaker half-open forever
    assert not board.allow("m")
    board.record_inconclusive("m")
    assert board.allow("m")
    assert not board.allow("m")


def test_burst_level_failure_counts_once_toward_breaker():
    """One incident on an N-job coalesced burst (e.g. a deadline expiry
    during a cold compile) is ONE consecutive failure, not N — it must
    not single-handedly quarantine the model."""

    async def scenario():
        executor = ChaoticExecutor(hang_s=30.0)
        registry = ModelRegistry(catalog=[], allow_random=True)
        worker = _worker(chaos_settings(job_deadline_s=0.05),
                         executor, registry=registry)
        jobs = [_cjob(f"bt{i}", chaos=["hang"], model="one/model")
                for i in range(3)]  # breaker threshold is 2
        results = await worker._execute_burst(jobs, StubSlot())
        assert [r["pipeline_config"]["error_kind"] for r in results] == \
            ["timeout"] * 3
        assert not registry.is_quarantined("one/model")
        breakers = worker.health()["breakers"]
        assert breakers["one/model"]["consecutive_failures"] == 1

    asyncio.run(scenario())


def test_model_unavailable_redispatchable_but_still_breaker_fodder():
    """ISSUE 6 satellite (resolves the PR-2 taxonomy tension): a
    node-local model-unavailable uploads WITHOUT the fatal flag and with
    ``error_kind=model_unavailable`` — the hive may redispatch it — yet
    it still counts toward the model's circuit breaker, so K misses in a
    row quarantine the checkpoint locally exactly as before."""
    from chiaswarm_tpu.node.executor import synchronous_do_work
    from chiaswarm_tpu.node.resilience import BREAKER_KINDS, REDISPATCH_KINDS

    assert "model_unavailable" in BREAKER_KINDS
    assert "model_unavailable" in REDISPATCH_KINDS
    assert "quarantined" in REDISPATCH_KINDS

    # the REAL executor path: a registry without the model raises the
    # load ValueError; the envelope must be non-fatal + redispatchable
    registry = ModelRegistry(catalog=[], allow_random=False)
    result = synchronous_do_work(
        _cjob("mu-1", model="not/served"), StubSlot(), registry)
    config = result["pipeline_config"]
    assert config["error_kind"] == "model_unavailable"
    assert "fatal_error" not in result  # the hive may redispatch

    async def breaker_still_quarantines():
        executor = ChaoticExecutor()
        reg = ModelRegistry(catalog=[], allow_random=True)
        worker = _worker(chaos_settings(), executor, registry=reg)
        bad = "missing/checkpoint"

        async def refuse(job, slot, registry):
            return {
                "id": job.get("id"),
                "artifacts": {},
                "pipeline_config": {
                    "error": "model is not available on this node",
                    "error_kind": "model_unavailable"},
            }

        executor.do_work = refuse  # threshold is 2
        for i in range(2):
            [envelope] = await worker._execute_burst(
                [_cjob(f"mu{i}", model=bad)], StubSlot())
            assert classify_result(envelope) == "model_unavailable"
        assert reg.is_quarantined(bad)
        assert worker.health()["breakers"][bad]["state"] == "open"
        # and the refusal envelope of the OPEN breaker is itself
        # redispatchable (kind "quarantined", non-fatal)
        [refused] = await worker._execute_burst(
            [_cjob("mu2", model=bad)], StubSlot())
        assert refused["pipeline_config"]["error_kind"] == "quarantined"
        assert "fatal_error" not in refused

    asyncio.run(breaker_still_quarantines())


def test_breaker_ignores_user_input_errors():
    """K bad *requests* in a row must not quarantine a healthy model."""

    async def scenario():
        executor = ChaoticExecutor()
        registry = ModelRegistry(catalog=[], allow_random=True)
        worker = _worker(chaos_settings(), executor, registry=registry)
        model = "healthy/model"
        for i in range(4):  # threshold is 2; fatal kinds never count
            await worker._execute_burst(
                [_cjob(f"u{i}", chaos=["fatal"], model=model)], StubSlot())
        assert not registry.is_quarantined(model)
        assert worker.health()["breakers"] == {}

    asyncio.run(scenario())


def test_crashed_burst_reports_an_envelope_per_job():
    """A crash escaping the executor (reference behavior: job silently
    eaten, hive times out) must yield one explicit error envelope per
    burst member through the normal result path."""

    async def scenario():
        executor = ChaoticExecutor()
        slot = StubSlot(depth=1, data_width=4)
        worker = _worker(chaos_settings(), executor, slots=[slot])
        jobs = [_cjob(f"x{i}", chaos=["crash"], model="tiny")
                for i in range(3)]
        for job in jobs:
            worker.work_queue.put_nowait(job)
        task = asyncio.create_task(worker._slot_worker(slot))
        await asyncio.wait_for(worker.work_queue.join(), timeout=10)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        envelopes = []
        while not worker.result_queue.empty():
            envelopes.append(worker.result_queue.get_nowait())
        got = sorted(e["id"] for e in envelopes)
        assert got == ["x0", "x1", "x2"]
        for envelope in envelopes:
            assert envelope["pipeline_config"]["error_kind"] == "error"
            assert "chaos: executor crash" in \
                envelope["pipeline_config"]["error"]

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# graceful shutdown + durability (satellites)
# ---------------------------------------------------------------------------


def test_shutdown_drains_inflight_burst_and_uploads_result():
    """Stop while a job is mid-execution: the burst completes and its
    result uploads BEFORE run() returns — chip time already spent is
    never discarded by shutdown."""

    async def scenario():
        hive = ChaoticHive()
        uri = await hive.start()
        executor = ChaoticExecutor(slow_s=0.4)
        hive.submit(_cjob("c-slow", chaos=["slow"]))
        worker = Worker(settings=chaos_settings(uri, job_deadline_s=10.0),
                        pool=[StubSlot()],
                        registry=ModelRegistry(catalog=[],
                                               allow_random=True),
                        executor=executor)
        task = asyncio.create_task(worker.run())
        try:
            await asyncio.wait_for(executor.started.wait(), timeout=30)
            worker.request_stop()  # job is in flight RIGHT NOW
            await asyncio.wait_for(task, timeout=20)
        finally:
            await hive.stop()
        assert hive.uploaded_ids() == ["c-slow"]  # uploaded before exit
        assert worker.dead_letters.depth() == 0

    asyncio.run(scenario())


def test_forced_cancel_requeues_held_job():
    """A job claimed by the burst drain but never dispatched (the held
    mismatch) must return to the queue on forced cancellation — never be
    dropped."""

    async def scenario():
        executor = ChaoticExecutor(hang_s=30.0)
        worker = _worker(chaos_settings(job_deadline_s=100.0), executor,
                         slots=[StubSlot(depth=1, data_width=4)])
        job_a = _cjob("A", chaos=["hang"], model="tiny")
        # key mismatch -> held: size splits the burst key even with lanes
        # on (steps/guidance/strength relax when the stepper rides them
        # per row, ISSUE 7 — a size mismatch never relaxes)
        job_b = _cjob("B", chaos=["ok"], model="tiny", height=128)
        worker.work_queue.put_nowait(job_a)
        worker.work_queue.put_nowait(job_b)
        task = asyncio.create_task(worker._slot_worker(worker.pool[0]))
        await asyncio.wait_for(executor.started.wait(), timeout=10)
        await asyncio.sleep(0.05)  # A hangs in flight; B is held
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        assert worker.work_queue.qsize() == 1
        assert worker.work_queue.get_nowait()["id"] == "B"

    asyncio.run(scenario())


def test_unsent_results_spool_and_replay_on_next_start(tmp_path):
    """Durability across restarts: an envelope that exhausted its upload
    retries lands in the dead-letter directory; the NEXT worker startup
    replays and uploads it, then removes the file."""

    async def scenario():
        from chiaswarm_tpu.node.executor import error_result

        # the default spool is namespaced by worker name so one worker
        # can never replay-and-delete another's results
        spool = DeadLetterSpool(tmp_path / "dead_letter" / "chaos-worker")
        envelope = error_result({"id": "dl-1",
                                 "content_type": "application/json"},
                                "spooled by a previous run", kind="error")
        spool.spool(envelope)
        assert spool.depth() == 1

        hive = ChaoticHive()
        uri = await hive.start()
        worker = Worker(settings=chaos_settings(uri), pool=[StubSlot()],
                        registry=ModelRegistry(catalog=[],
                                               allow_random=True),
                        executor=ChaoticExecutor())
        task = asyncio.create_task(worker.run())
        try:
            await hive.wait_for_results(1, timeout=30)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)
            await hive.stop()
        assert hive.uploaded_ids() == ["dl-1"]
        assert worker.stats.results_replayed == 1
        assert spool.depth() == 0  # discarded after the upload succeeded

    asyncio.run(scenario())


def test_drain_with_fewer_jobs_than_slots_exits_promptly():
    """Two slots racing for the last queued job during drain: the loser
    must notice the queue went dry and exit instead of blocking the
    whole shutdown until the drain timeout force-cancels it."""

    async def scenario():
        executor = ChaoticExecutor()
        slots = [StubSlot(name="s0"), StubSlot(name="s1")]
        worker = _worker(chaos_settings(), executor, slots=slots)
        tasks = [asyncio.create_task(worker._slot_worker(s))
                 for s in slots]
        for _ in range(5):  # both slots parked on the queue
            await asyncio.sleep(0)
        worker.work_queue.put_nowait(_cjob("last-one"))
        worker._draining.set()
        # well under drain_timeout_s (5s): the losing slot must not hang
        await asyncio.wait_for(asyncio.gather(*tasks), timeout=3.0)
        assert worker.result_queue.qsize() == 1
        assert worker.result_queue.get_nowait()["id"] == "last-one"

    asyncio.run(scenario())


def test_poll_loop_full_queue_respects_stop():
    """Satellite: the poll loop's backpressure wait must observe _stop —
    a full work queue can no longer delay shutdown indefinitely."""

    async def scenario():
        worker = _worker(chaos_settings(), ChaoticExecutor(),
                         slots=[StubSlot(depth=1, data_width=1)])
        worker.work_queue.put_nowait(_cjob("fill"))  # maxsize 1 -> full
        assert worker.work_queue.full()
        task = asyncio.create_task(worker._poll_loop())
        await asyncio.sleep(0.1)  # parked in the backpressure wait
        worker.request_stop()
        await asyncio.wait_for(task, timeout=2.0)  # returns, not cancelled

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# hive client + resilience primitives (satellites)
# ---------------------------------------------------------------------------


def test_get_work_nonjson_400_still_raises_bad_worker():
    """Satellite: a misbehaving-worker signal with a non-JSON body must
    stay a BadWorkerError, not demote to a generic poll failure."""

    async def scenario():
        import aiohttp

        hive = ChaoticHive(poll_faults=["bad_worker"])
        uri = await hive.start()
        try:
            client = HiveClient(uri, "t", "w")
            async with aiohttp.ClientSession() as session:
                with pytest.raises(BadWorkerError, match="bad worker"):
                    await client.get_work(session)
        finally:
            await hive.stop()

    asyncio.run(scenario())


def test_poll_backoff_grows_caps_and_resets():
    """Satellite: capped exponential backoff + jitter replaces the flat
    121 s error delay; the schedule resets on the first success."""
    backoff = Backoff(base=2.0, cap=121.0, seed="poll:test")
    delays = [backoff.next() for _ in range(10)]
    assert 1.0 <= delays[0] <= 2.0  # equal jitter around the base
    assert all(d <= 121.0 for d in delays)
    assert max(delays[6:]) > 30.0   # actually grew toward the cap
    backoff.reset()
    assert 1.0 <= backoff.next() <= 2.0
    # determinism: same seed -> same schedule (chaos reproducibility)
    again = Backoff(base=2.0, cap=121.0, seed="poll:test")
    assert [again.next() for _ in range(10)] == delays


def test_classify_exception_taxonomy():
    import requests

    assert classify_exception(ValueError("max image size")) == "fatal"
    assert classify_exception(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "oom"
    # ISSUE 6: node-local model-unavailable is a redispatch signal, not
    # a fatal user-input error (the hive routes it to another worker)
    assert classify_exception(
        ValueError("model 'x' is not available on this node")) == \
        "model_unavailable"
    assert classify_exception(ConnectionResetError("peer")) == "transient"
    assert classify_exception(
        requests.exceptions.ConnectTimeout("slow cdn")) == "transient"
    assert classify_exception(requests.exceptions.HTTPError(
        "503 Server Error: upstream")) == "transient"
    assert classify_exception(requests.exceptions.HTTPError(
        "404 Client Error: gone")) == "fatal"
    # 5xx-looking digits in the URL must not fool the classifier
    assert classify_exception(requests.exceptions.HTTPError(
        "404 Client Error: Not Found for url: "
        "https://cdn/500x500/a.png")) == "fatal"
    assert classify_exception(KeyError("wat")) == "error"
    # deterministic jitter helper stays within the envelope
    import random as _random
    rng = _random.Random(7)
    for attempt in range(1, 12):
        delay = backoff_delay(attempt, 0.5, 30.0, rng)
        assert 0.0 < delay <= 30.0


def test_malformed_job_through_real_executor_is_fatal_envelope():
    """The real formatting path contains garbage jobs as fatal envelopes
    (the chaos hive's 'malformed' mode rides the same shape)."""
    from chiaswarm_tpu.node.chaos import _malformed_job
    from chiaswarm_tpu.node.executor import synchronous_do_work

    registry = ModelRegistry(catalog=[], allow_random=True)
    result = synchronous_do_work(_malformed_job(1), StubSlot(), registry)
    assert result["id"] == "malformed-1"
    assert result["fatal_error"] is True
    assert result["pipeline_config"]["error_kind"] == "fatal"


def test_transient_format_failure_is_not_fatal():
    """An input-image fetch blip during formatting uploads WITHOUT the
    fatal flag (and tagged transient) so the ladder/hive may retry it —
    only genuinely bad inputs are fatal."""
    from chiaswarm_tpu.node.executor import synchronous_do_work

    registry = ModelRegistry(catalog=[], allow_random=True)
    job = _cjob("fetch-blip", model="tiny",
                start_image_uri="http://127.0.0.1:9/never-listens.png")
    result = synchronous_do_work(job, StubSlot(), registry)
    config = result["pipeline_config"]
    assert "error" in config
    assert config["error_kind"] == "transient"
    assert "fatal_error" not in result

    async def retries_then_succeeds():
        # the worker-side ladder picks the transient envelope up and
        # re-runs; here the re-run is scripted to succeed
        executor = ChaoticExecutor()
        worker = _worker(chaos_settings(), executor)
        [final] = await worker._execute_burst(
            [_cjob("fb2", chaos=["fetch", "ok"])], StubSlot())
        assert classify_result(final) == "ok"

    asyncio.run(retries_then_succeeds())


def test_breaker_state_persists_across_restarts():
    """ISSUE 4 satellite (ROADMAP PR-2 candidate): a model quarantined
    before a restart is still quarantined after it — the breaker board
    serializes open breakers next to the dead-letter spool and a fresh
    worker on the same settings root reloads them (and re-mirrors the
    registry quarantine) without a single new failure."""

    async def scenario():
        executor = ChaoticExecutor()
        registry = ModelRegistry(catalog=[], allow_random=True)
        settings = chaos_settings()  # threshold 2, cooldown 3600
        worker1 = _worker(settings, executor, registry=registry)
        bad = "bad/checkpoint"
        for i in range(2):
            await worker1._execute_burst(
                [_cjob(f"bp{i}", chaos=["crash"], model=bad)], StubSlot())
        assert registry.is_quarantined(bad)
        assert worker1._breaker_state_path().is_file()

        # "restart": fresh worker AND fresh registry on the same root
        registry2 = ModelRegistry(catalog=[], allow_random=True)
        worker2 = _worker(settings, executor, registry=registry2)
        assert registry2.is_quarantined(bad)  # restored at construction
        assert worker2.health()["breakers"][bad]["state"] == "open"
        [refused] = await worker2._execute_burst(
            [_cjob("bp2", chaos=["ok"], model=bad)], StubSlot())
        assert refused["pipeline_config"]["error_kind"] == "quarantined"
        assert "bp2" not in executor.attempts  # no chip time burned

        # a successful probe after the cooldown clears the state file
        worker2.breakers = BreakerBoard(
            threshold=2, cooldown_s=0.0,
            on_open=registry2.quarantine, on_close=registry2.unquarantine,
            on_probe=registry2.unquarantine,
            persist_path=worker2._breaker_state_path())
        [probe] = await worker2._execute_burst(
            [_cjob("bp3", chaos=["ok"], model=bad)], StubSlot())
        assert classify_result(probe) == "ok"
        assert not worker2._breaker_state_path().is_file()

    asyncio.run(scenario())


def test_breaker_persistence_restores_remaining_cooldown(tmp_path):
    """The monotonic clock dies with the process, so the file carries
    the REMAINING cooldown: save() at shutdown refreshes it and the
    restored breaker re-opens for exactly that residue."""
    clock = [100.0]
    path = tmp_path / "breakers.json"
    board = BreakerBoard(threshold=1, cooldown_s=50.0,
                         clock=lambda: clock[0], persist_path=path)
    board.record("m", ok=False)  # opens at t=100; file says remaining 50
    clock[0] = 120.0
    board.save()                 # clean shutdown: remaining 30

    clock2 = [1000.0]            # new process, new monotonic epoch
    board2 = BreakerBoard(threshold=1, cooldown_s=50.0,
                          clock=lambda: clock2[0], persist_path=path)
    assert board2.states()["m"]["state"] == "open"
    assert not board2.allow("m")
    clock2[0] = 1029.0           # 29s later: still inside the residue
    assert not board2.allow("m")
    clock2[0] = 1031.0           # residue elapsed: half-open probe
    assert board2.allow("m")

    # a corrupt state file must not break startup
    path.write_text("{not json", encoding="utf-8")
    board3 = BreakerBoard(threshold=1, cooldown_s=50.0,
                          clock=lambda: clock2[0], persist_path=path)
    assert board3.states() == {}


@pytest.mark.slow
def test_chaos_soak_zero_loss_from_seed():
    """Nightly soak (ISSUE 4 satellite): a LONG randomized fault script
    expanded from a seed (CHIASWARM_SOAK_SEED, defaulting stable for
    local runs; nightly CI passes the run id) drives a real worker
    through poll faults, executor faults, and upload faults at once —
    and the PR-2 invariant must hold at scale: every issued job settles
    as exactly one uploaded envelope or one dead-letter file."""
    import os
    import random

    from chiaswarm_tpu.node.chaos import ChaosSchedule

    seed = os.environ.get("CHIASWARM_SOAK_SEED", "soak-default")
    n_jobs = int(os.environ.get("CHIASWARM_SOAK_JOBS", "60"))
    rng = random.Random(f"chaos-soak:{seed}")

    # every script terminates in a deterministic envelope: ok, a
    # recovered retry, a fatal, a crash envelope, or a deadline timeout
    outcome_scripts = (
        (["ok"], 6),
        (["oom", "ok"], 2),
        (["fetch", "ok"], 2),
        (["fetch", "fetch", "ok"], 1),
        (["crash"], 1),
        (["fatal"], 1),
        (["hang"], 1),
        (["slow"], 1),
    )
    weighted = [script for script, w in outcome_scripts for _ in range(w)]
    jobs = [_cjob(f"soak-{i}", chaos=list(rng.choice(weighted)))
            for i in range(n_jobs)]

    # upload-side faults for a seeded subset; a couple exhaust every
    # retry and MUST land in the dead-letter spool
    result_faults: dict[str, list[str]] = {}
    flaky = rng.sample([j["id"] for j in jobs], k=max(2, n_jobs // 6))
    dead_ids = set(flaky[:2])
    for job_id in flaky:
        if job_id in dead_ids:
            result_faults[job_id] = ["http_500"] * 10
        else:
            result_faults[job_id] = [rng.choice(["http_500", "drop"]), "ok"]

    poll_faults = ChaosSchedule.from_seed(
        f"poll:{seed}",
        ("ok", "ok", "ok", "drop", "delay", "http_500", "malformed"),
        length=n_jobs)

    async def scenario():
        hive = ChaoticHive(poll_faults=poll_faults._script,
                           result_faults=result_faults, delay_s=0.01)
        uri = await hive.start()
        for job in jobs:
            hive.submit(job)
        executor = ChaoticExecutor(hang_s=1.0, slow_s=0.05)
        worker = Worker(settings=chaos_settings(uri), pool=[StubSlot()],
                        registry=ModelRegistry(catalog=[],
                                               allow_random=True),
                        executor=executor)
        task = asyncio.create_task(worker.run())
        try:
            deadline = asyncio.get_running_loop().time() + 300
            while asyncio.get_running_loop().time() < deadline:
                settled = len(hive.results) + worker.dead_letters.depth()
                if settled >= len(hive.issued_ids) and \
                        len(hive.results) >= len(hive.issued_ids) - \
                        len(dead_ids):
                    break
                await asyncio.sleep(0.1)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=30)
            await hive.stop()

        uploaded = hive.uploaded_ids()
        dead = {json.loads(p.read_text())["id"]
                for p in worker.dead_letters.directory.glob("*.json")}
        issued = set(hive.issued_ids)
        # the zero-loss invariant, at soak scale: exactly-once settling
        assert len(uploaded) == len(set(uploaded)), "duplicate uploads"
        assert set(uploaded) | dead == issued
        assert set(uploaded) & dead == set()
        assert dead == dead_ids

    asyncio.run(scenario())


def test_mid_lane_fault_keeps_zero_loss(monkeypatch):
    """ISSUE 3: a crash/OOM injected into a RUNNING step-scheduler lane
    (serving/stepper.py) with spliced rows resident must not lose a job:
    every row's future fails, the executor bounces each job to the
    per-job path, and every id uploads exactly one envelope through a
    real Worker loop."""
    import sys

    sys.path.insert(0, "tests")
    from fake_hive import FakeHive

    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.serving.stepper import get_stepper

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])
    slot = pool.slots[0]
    stepper = get_stepper(slot)
    # the fault fires DURING the lane's denoise loop, after the rows of
    # this burst have been admitted (mid-flight, not at submit time)
    stepper.inject_fault(
        after_steps=stepper.stats().get("steps_executed", 0) + 1,
        exc=RuntimeError("RESOURCE_EXHAUSTED: chaos mid-lane"))

    async def scenario():
        hive = FakeHive()
        await hive.start()
        for i in range(3):
            hive.jobs.append({
                "id": f"lane-{i}", "model_name": "tiny",
                "prompt": f"p{i}", "seed": 500 + i,
                # mixed steps: only a lane (relaxed key) can merge these
                "num_inference_steps": 2 + i,
                "height": 64, "width": 64, "content_type": "image/png"})
        worker = Worker(
            settings=chaos_settings(hive.uri, job_deadline_s=600.0,
                                    workflow_deadline_s={}),
            registry=registry, pool=pool)
        task = asyncio.create_task(worker.run())
        try:
            await hive.wait_for_results(3, timeout=300)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=30)
            await hive.stop()
        return hive.results

    results = asyncio.run(scenario())
    by_id = {r["id"]: r for r in results}
    # exactly-once: all three ids, no duplicates, no silent drops
    assert sorted(by_id) == ["lane-0", "lane-1", "lane-2"]
    assert len(results) == 3
    for r in results:
        # the fallback path served every bounced row successfully
        assert r["pipeline_config"].get("error") is None, r
        assert "fatal_error" not in r
    assert stepper.stats().get("lanes_failed", 0) >= 1


# ---------------------------------------------------------------------------
# ISSUE 8: the budget-squeeze fault — residency churn under the chaos
# harness (evict -> reload -> degraded load-per-job -> bounce/redispatch)
# ---------------------------------------------------------------------------


def _residency_worker_parts(budget_bytes, hard_bytes, models,
                            monkeypatch):
    """Real tiny pipelines + a private residency ledger + a single-chip
    pool — the substrate both squeeze tests share. Lanes are opted out:
    a lane holds its pipe between jobs, which would blur the ledger
    accounting these tests assert exactly."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec
    from chiaswarm_tpu.obs.metrics import Registry as ObsRegistry
    from chiaswarm_tpu.serving.residency import ResidencyManager

    monkeypatch.setenv("CHIASWARM_STEPPER", "0")
    manager = ResidencyManager(budget_bytes=budget_bytes,
                               hard_limit_bytes=hard_bytes,
                               metrics_registry=ObsRegistry(),
                               persist_path=None, reserve_wait_s=0.2)
    registry = ModelRegistry(
        catalog=[{"name": name, "family": "tiny"} for name in models],
        allow_random=True, residency=manager)
    pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                    devices=jax.devices()[:1])
    return manager, registry, pool


def test_budget_squeeze_churn_zero_loss(monkeypatch):
    """ISSUE 8 satellite: a scripted budget squeeze while a mixed-model
    stream flows — models churn through every rung (resident -> evicted
    -> reloaded -> degraded load-per-job -> model_unavailable bounce)
    and NO job is lost: every id settles as exactly one envelope, the
    bounce uploads non-fatal model_unavailable (redispatchable, PR 6),
    and peak ledger bytes never exceed budget + one model."""
    import sys

    sys.path.insert(0, "tests")
    from fake_hive import FakeHive

    models = ["tiny/a", "tiny/b"]
    # probe one load to denominate the budget in measured bytes
    probe_mgr, probe_reg, _ = _residency_worker_parts(
        1 << 30, 2 << 30, ["tiny/probe"], monkeypatch)
    probe_reg.pipeline("tiny/probe")
    footprint = probe_mgr.measured_footprints()["tiny/probe"]

    budget = int(footprint * 1.5)
    manager, registry, pool = _residency_worker_parts(
        budget, footprint * 4, models, monkeypatch)
    manager.reset_peak()

    async def scenario():
        hive = FakeHive()
        await hive.start()
        worker = Worker(
            settings=chaos_settings(hive.uri, job_deadline_s=600.0,
                                    workflow_deadline_s={}),
            registry=registry, pool=pool)
        task = asyncio.create_task(worker.run())
        try:
            # phase 1: alternate models under the tight budget — churn.
            # One job at a time: a depth-2 slot would otherwise load
            # both models concurrently and make the eviction count
            # depend on admit order.
            for i in range(3):
                hive.jobs.append(
                    {"id": f"sq-{i}", "model_name": models[i % 2],
                     "prompt": f"p{i}", "seed": 40 + i,
                     "num_inference_steps": 2, "height": 64, "width": 64,
                     "content_type": "image/png"})
                await hive.wait_for_results(i + 1, timeout=600)
            # phase 2: SQUEEZE below one model — the next job must
            # degrade to load-per-job, not fail
            manager.set_budget(int(footprint * 0.5))
            hive.jobs.append(
                {"id": "sq-degraded", "model_name": models[0],
                 "prompt": "pd", "seed": 50, "num_inference_steps": 2,
                 "height": 64, "width": 64,
                 "content_type": "image/png"})
            await hive.wait_for_results(4, timeout=600)
            # phase 3: squeeze the HARD limit below one model — the job
            # bounces model_unavailable for the hive to redispatch
            manager.set_budget(int(footprint * 0.5),
                               hard_limit_bytes=int(footprint * 0.6))
            hive.jobs.append(
                {"id": "sq-bounce", "model_name": models[1],
                 "prompt": "pb", "seed": 51, "num_inference_steps": 2,
                 "height": 64, "width": 64,
                 "content_type": "application/json"})
            await hive.wait_for_results(5, timeout=600)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=60)
            await hive.stop()
        return hive.results

    results = asyncio.run(scenario())
    by_id = {r["id"]: r for r in results}
    # zero loss: every id exactly once
    assert sorted(by_id) == ["sq-0", "sq-1", "sq-2", "sq-bounce",
                             "sq-degraded"]
    assert len(results) == 5
    for i in range(3):
        assert by_id[f"sq-{i}"]["pipeline_config"].get("error") is None
    degraded = by_id["sq-degraded"]["pipeline_config"]
    assert degraded.get("error") is None
    assert degraded.get("residency") == "per_job"
    bounce = by_id["sq-bounce"]
    assert bounce["pipeline_config"]["error_kind"] == "model_unavailable"
    assert "fatal_error" not in bounce  # a lease-aware hive redispatches
    from chiaswarm_tpu.node.resilience import REDISPATCH_KINDS

    assert bounce["pipeline_config"]["error_kind"] in REDISPATCH_KINDS
    # the ledger churned within its invariant
    snap = manager.snapshot()
    assert snap["evictions"] >= 2
    assert snap["degraded_loads"] >= 1
    assert snap["bounces"] >= 1
    largest = max(manager.measured_footprints().values())
    assert manager.peak_bytes <= budget + largest


@pytest.mark.slow
def test_residency_squeeze_soak_zero_loss(monkeypatch):
    """Nightly residency soak (ISSUE 8 satellite, runs in the chaos-soak
    workflow's ``-k soak`` selection): a seeded mixed-model stream with
    randomized mid-run budget squeezes/restores. The gate is the
    zero-loss invariant plus the no-double-buffer peak bound, at soak
    scale."""
    import os
    import random
    import sys

    sys.path.insert(0, "tests")
    from fake_hive import FakeHive

    seed = os.environ.get("CHIASWARM_SOAK_SEED", "residency-default")
    # divided down from the chaos-soak job knob: unlike the stub-executor
    # soaks, every one of these jobs runs a REAL tiny pipeline, and every
    # swap recompiles — ~10x the per-job cost
    n_jobs = max(8, int(os.environ.get("CHIASWARM_SOAK_JOBS", "120")) // 10)
    rng = random.Random(f"residency-soak:{seed}")

    models = ["tiny/a", "tiny/b", "tiny/c"]
    probe_mgr, probe_reg, _ = _residency_worker_parts(
        1 << 30, 2 << 30, ["tiny/probe"], monkeypatch)
    probe_reg.pipeline("tiny/probe")
    footprint = probe_mgr.measured_footprints()["tiny/probe"]
    budget = int(footprint * 1.7)
    manager, registry, pool = _residency_worker_parts(
        budget, footprint * 4, models, monkeypatch)
    manager.reset_peak()

    async def scenario():
        hive = FakeHive()
        await hive.start()
        worker = Worker(
            settings=chaos_settings(hive.uri, job_deadline_s=600.0,
                                    workflow_deadline_s={}),
            registry=registry, pool=pool)
        task = asyncio.create_task(worker.run())
        try:
            done = 0
            for i in range(n_jobs):
                hive.jobs.append(
                    {"id": f"rsoak-{i}",
                     "model_name": rng.choice(models),
                     "prompt": f"p{i}", "seed": 7000 + i,
                     "num_inference_steps": 2, "height": 64,
                     "width": 64, "content_type": "image/png"})
                done += 1
                await hive.wait_for_results(done, timeout=600)
                # seeded squeezes: shrink below one model (degrade) or
                # restore; the stream must keep settling either way
                roll = rng.random()
                if roll < 0.25:
                    manager.set_budget(int(footprint * 0.5))
                elif roll < 0.5:
                    manager.set_budget(budget)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=60)
            await hive.stop()
        return hive.results

    results = asyncio.run(scenario())
    ids = [r["id"] for r in results]
    assert len(ids) == len(set(ids)) == n_jobs  # exactly once, no loss
    for r in results:
        assert r["pipeline_config"].get("error") is None, r
    largest = max(manager.measured_footprints().values())
    assert manager.peak_bytes <= budget + largest
