"""Fleet-scale fault tolerance (ISSUE 6): the MiniHive lease protocol
and multi-worker chaos.

Three layers:

- **Protocol units** (fake clock, no workers): lease grant/extend/
  expiry, redelivery with the dead worker excluded, heartbeat checkpoint
  custody (stale senders rejected), exactly-once settling under double
  uploads, and redispatch on ``error_kind=model_unavailable``.
- **Fleet chaos** (real Workers + ChaoticExecutor, no pipelines): a
  partition outliving the lease makes the presumed-dead worker's late
  upload race the redelivered completion — exactly one is acked; a
  worker killed mid-job loses nothing.
- **The acceptance gate** (real lanes): 3 workers on one mini-hive, one
  killed mid-lane — every in-flight job completes exactly once, and the
  redelivered job provably resumes from checkpoint step >= 1 (asserted
  via its resume-step metric/span), not from step 0.

Everything is hermetic (loopback only) and scripted/seeded.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from chiaswarm_tpu.node.chaos import ChaoticExecutor
from chiaswarm_tpu.node.executor import error_result
from chiaswarm_tpu.node.hivelog import HiveJournal
from chiaswarm_tpu.node.minihive import MiniHive, result_error_kind
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.settings import Settings
from chiaswarm_tpu.node.worker import Worker


@pytest.fixture(autouse=True)
def _tmp_root(tmp_path, monkeypatch):
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path))
    return tmp_path


class StubSlot:
    def __init__(self, depth: int = 2, data_width: int = 1,
                 name: str = "stub"):
        self.depth = depth
        self.data_width = data_width
        self.name = name

    def descriptor(self):
        return self.name


def fleet_settings(uri: str, name: str, **over) -> Settings:
    base = dict(
        hive_uri=uri, hive_token="t", worker_name=name,
        job_deadline_s=0.5,
        transient_retries=2,
        retry_backoff_s=0.01, retry_backoff_cap_s=0.05,
        breaker_threshold=3, breaker_cooldown_s=3600.0,
        poll_busy_s=0.02, poll_idle_s=0.04,
        poll_backoff_base_s=0.02, poll_backoff_cap_s=0.1,
        upload_retries=3, upload_retry_delay_s=0.02,
        drain_timeout_s=5.0, result_drain_timeout_s=5.0,
        install_signal_handlers=False,
        heartbeat_s=0.1,
    )
    base.update(over)
    return Settings(**base)


def _job(job_id: str, chaos=None, model: str = "shared/tiny", **over):
    job = {"id": job_id, "model_name": model, "prompt": f"p {job_id}",
           "num_inference_steps": 2, "height": 64, "width": 64,
           "content_type": "application/json"}
    if chaos is not None:
        job["chaos"] = chaos
    job.update(over)
    return job


def _ok_result(job_id: str, worker: str = "") -> dict:
    result = {"id": job_id, "artifacts": {}, "nsfw": False,
              "pipeline_config": {"mode": "test"}}
    if worker:
        result["worker_name"] = worker
    return result


def _counter(hive: MiniHive, name: str) -> float:
    metric = hive.metrics.get(name)
    return 0.0 if metric is None else metric.value()


# ---------------------------------------------------------------------------
# protocol units (fake clock)
# ---------------------------------------------------------------------------


def test_lease_grant_extend_expire_redeliver_excludes_dead_worker():
    clock = [0.0]
    hive = MiniHive(lease_s=10.0, clock=lambda: clock[0])
    hive.submit(_job("j1"))

    [handed] = hive._take_jobs("wA")
    assert handed["id"] == "j1" and handed["attempt"] == 1
    assert "resume" not in handed  # nothing checkpointed yet
    assert hive.lease_holder("j1") == "wA"
    assert hive._take_jobs("wB") == []  # leased elsewhere

    clock[0] = 8.0
    hive._take_jobs("wA")  # a poll proves liveness: lease extends to 18
    clock[0] = 15.0
    assert hive.sweep() == []
    clock[0] = 19.0
    assert hive.sweep() == ["j1"]  # expired -> requeued

    # the dead worker is excluded; a live one gets attempt 2
    assert hive._take_jobs("wA") == []
    [redelivered] = hive._take_jobs("wB")
    assert redelivered["attempt"] == 2
    assert hive.lease_holder("j1") == "wB"

    # starvation valve: once EVERY known worker is excluded, exclusion
    # has nothing to route around and the job flows again
    clock[0] = 40.0
    assert hive.sweep() == ["j1"]
    assert hive.excluded["j1"] == {"wA", "wB"}
    [third] = hive._take_jobs("wA")
    assert third["attempt"] == 3

    assert _counter(hive, "chiaswarm_hive_leases_granted_total") == 3
    assert _counter(hive, "chiaswarm_hive_leases_expired_total") == 2
    assert _counter(hive, "chiaswarm_hive_jobs_redelivered_total") == 2


def test_max_attempts_abandons_instead_of_looping_forever():
    clock = [0.0]
    hive = MiniHive(lease_s=1.0, max_attempts=2, clock=lambda: clock[0])
    hive.submit(_job("j1"))
    for n, worker in enumerate(["wA", "wB"], start=1):
        [handed] = hive._take_jobs(worker)
        assert handed["attempt"] == n
        clock[0] += 2.0
        hive.sweep()
    assert hive.abandoned == ["j1"]
    assert hive._take_jobs("wC") == []  # parked, not redelivered
    assert _counter(hive, "chiaswarm_hive_jobs_abandoned_total") == 1


def test_heartbeat_extends_lease_and_owns_checkpoint_custody():
    """Heartbeats keep leases alive and carry resume checkpoints; a
    sender that lost its lease is told so, and its stale checkpoint must
    NOT shadow the new holder's progress."""

    async def scenario():
        import aiohttp

        clock = [0.0]
        hive = MiniHive(lease_s=1.0, clock=lambda: clock[0])
        await hive.start()
        try:
            hive.submit(_job("j1"))
            hive._take_jobs("wA")

            async with aiohttp.ClientSession() as session:
                async def beat(worker, ckpt):
                    async with session.post(
                            f"{hive.uri}/api/heartbeat",
                            json={"worker_name": worker,
                                  "jobs": [{"id": "j1",
                                            "checkpoint": ckpt}]}) as r:
                        return await r.json()

                # heartbeats past the original expiry keep the lease
                for _ in range(5):
                    clock[0] += 0.8
                    response = await beat("wA", {"kind": "lane", "step": 3})
                    assert response == {"status": "ok", "lost": []}
                assert hive.lease_holder("j1") == "wA"
                assert hive.checkpoints["j1"]["step"] == 3

                # silence past the lease: expiry + redelivery
                clock[0] += 1.5
                hive.sweep()
                [redelivered] = hive._take_jobs("wB")
                # the redelivered copy carries the dead worker's state
                assert redelivered["resume"] == {"kind": "lane", "step": 3}
                assert redelivered["attempt"] == 2

                # the resurrected worker's heartbeat: lease lost, stale
                # checkpoint rejected
                response = await beat("wA", {"kind": "lane", "step": 99})
                assert response["lost"] == ["j1"]
                assert hive.checkpoints["j1"]["step"] == 3
                assert _counter(
                    hive, "chiaswarm_hive_checkpoints_stale_total") == 1

                # a job that SETTLED is not "lost": an upload racing the
                # next beat must not read as phantom lease churn
                hive._record_result(_ok_result("j1", "wB"), "wB")
                response = await beat("wB", None)
                assert response == {"status": "ok", "lost": []}
        finally:
            await hive.stop()

    asyncio.run(scenario())


def test_exactly_once_under_double_upload():
    clock = [0.0]
    hive = MiniHive(lease_s=1.0, clock=lambda: clock[0])
    hive.submit(_job("j1"))
    hive._take_jobs("wA")
    clock[0] = 2.0
    hive.sweep()
    hive._take_jobs("wB")

    assert hive._record_result(_ok_result("j1", "wB"), "wB") == \
        {"status": "ok"}
    # the presumed-dead worker's late upload: acked, never counted
    assert hive._record_result(_ok_result("j1", "wA"), "wA") == \
        {"status": "duplicate"}
    assert hive.uploaded_ids() == ["j1"]
    assert [r["worker_name"] for r in hive.duplicate_results] == ["wA"]
    # the registry snapshot agrees with the lists (satellite 3 contract)
    assert _counter(hive, "chiaswarm_hive_results_completed_total") == 1
    assert _counter(hive, "chiaswarm_hive_results_duplicate_total") == 1
    assert hive.stats()["completed"] == 1

    # the inverse race: the LATE upload settles first, while the
    # redelivered copy is still queued — settling must withdraw it so
    # no worker burns a full re-execution on a finished job
    hive.submit(_job("j2"))
    hive._take_jobs("wA")
    clock[0] = 4.0
    assert hive.sweep() == ["j2"]          # requeued for redelivery
    assert hive._record_result(_ok_result("j2", "wA"), "wA") == \
        {"status": "ok"}                   # late upload wins anyway
    assert hive._take_jobs("wB") == []     # queued copy withdrawn
    assert hive.stats()["pending"] == 0
    assert sorted(hive.uploaded_ids()) == ["j1", "j2"]


def test_redispatch_on_model_unavailable_error_kind():
    """The resolved taxonomy tension, hive side: a model_unavailable
    envelope does not settle the job — it requeues with the refusing
    worker excluded; a worker that HAS the model then serves it."""
    clock = [0.0]
    hive = MiniHive(lease_s=30.0, clock=lambda: clock[0])
    hive.submit(_job("j1", model="only/on-wB"))
    hive._take_jobs("wA")
    assert hive._take_jobs("wB") == []  # wB is known, j1 is leased

    refusal = error_result(_job("j1"), "model 'only/on-wB' is not "
                           "available on this node",
                           kind="model_unavailable")
    assert result_error_kind(refusal) == "model_unavailable"
    ack = hive._record_result(refusal, "wA")
    assert ack == {"status": "requeued", "kind": "model_unavailable"}
    assert hive.uploaded_ids() == []  # NOT settled
    assert hive._take_jobs("wA") == []  # refuser excluded
    [handed] = hive._take_jobs("wB")
    assert handed["attempt"] == 2
    assert hive._record_result(_ok_result("j1", "wB"), "wB") == \
        {"status": "ok"}
    assert hive.uploaded_ids() == ["j1"]
    assert hive.metrics.get("chiaswarm_hive_jobs_redispatched_total") \
        .value(kind="model_unavailable") == 1

    # a FATAL envelope settles immediately: bad inputs follow the job,
    # redispatching them would just burn another node's time
    hive.submit(_job("j2"))
    hive._take_jobs("wA")
    fatal = error_result(_job("j2"), "bad inputs", kind="fatal",
                         fatal=True)
    assert hive._record_result(fatal, "wA") == {"status": "ok"}
    assert sorted(hive.uploaded_ids()) == ["j1", "j2"]

    # a LATE refusal — its lease already expired and sweep requeued the
    # job — must not settle the error (and must not strip the queued
    # copy): the refuser is excluded, the live copy owns the outcome
    late = MiniHive(lease_s=1.0, clock=lambda: clock[0])
    clock[0] = 100.0
    late.submit(_job("j4", model="only/on-wB"))
    late._take_jobs("wA")
    clock[0] = 102.0
    assert late.sweep() == ["j4"]          # expired -> requeued
    ack = late._record_result(
        error_result(_job("j4"), "nope", kind="model_unavailable"), "wA")
    assert ack == {"status": "requeued", "kind": "model_unavailable"}
    assert late.uploaded_ids() == []       # NOT settled
    [handed] = late._take_jobs("wB")       # still deliverable
    assert handed["id"] == "j4"

    # redispatch is bounded by max_attempts: the last refusal settles
    bounded = MiniHive(lease_s=30.0, max_attempts=2,
                       clock=lambda: clock[0])
    bounded.submit(_job("j3", model="nowhere"))
    bounded._take_jobs("wA")
    assert bounded._record_result(
        error_result(_job("j3"), "nope", kind="model_unavailable"),
        "wA")["status"] == "requeued"
    bounded._take_jobs("wB")
    assert bounded._record_result(
        error_result(_job("j3"), "nope", kind="model_unavailable"),
        "wB") == {"status": "ok"}  # attempts exhausted: settle the error
    assert bounded.uploaded_ids() == ["j3"]


@pytest.mark.parametrize("restart", [False, True],
                         ids=["static", "hive_restart"])
def test_stats_reconciliation_exactly_once_at_harness_scale(
        restart, tmp_path):
    """ISSUE 9 satellite: the ``GET /api/stats`` registry snapshot stays
    exactly-once-consistent at swarmload scale — thousands of settled
    jobs churned through 4 rotating workers on a fake clock, with
    duplicates, late uploads after redelivery, overload/model refusals,
    and lease-expiry abandonment injected throughout. The counters must
    reconcile with the settle lists to the job.

    The ``hive_restart`` variant (ISSUE 14 satellite) journals the run
    and crashes the hive mid-churn — the replacement is rebuilt purely
    by journal replay (counters included) and the SAME reconciliation
    must hold across the restart, to the job."""
    clock = [0.0]
    journal_dir = tmp_path / "recon-hive"
    hive = MiniHive(lease_s=5.0, max_attempts=3, max_jobs_per_poll=8,
                    clock=lambda: clock[0],
                    journal=(HiveJournal(journal_dir, fsync=False)
                             if restart else None))
    n = 3000
    for i in range(n):
        hive.submit(_job(f"scale-{i}"))
    workers = [f"w{k}" for k in range(4)]
    rng = __import__("random").Random("scale-recon")

    injected_dupes = 0
    late_uploads = 0
    salvaged = 0
    refusals = 0
    step = 0
    restarted = False

    def record(result, worker):
        # mirror the salvage bookkeeping: ANY settle landing on an
        # abandoned job (a straggler upload — incl. a lease that a
        # mid-batch clock jump expired before its upload was recorded)
        # must move it abandoned -> completed, counted once
        nonlocal salvaged
        was_abandoned = str(result.get("id")) in hive.abandoned
        ack = hive._record_result(result, worker)
        if was_abandoned and ack.get("status") == "ok":
            salvaged += 1
        return ack

    while True:
        clock[0] += 0.5
        if restart and not restarted and len(hive.completed) >= n // 2:
            # the mid-churn crash (ISSUE 14): the live hive object is
            # garbage from here — the replacement is rebuilt purely by
            # journal replay, counters included, and the reconciliation
            # below must hold across the epoch bump
            hive.journal = None  # SIGKILL: nothing else ever commits
            hive = MiniHive.recover(
                HiveJournal(journal_dir, fsync=False),
                lease_s=5.0, max_attempts=3, max_jobs_per_poll=8,
                clock=lambda: clock[0])
            restarted = True
        worker = workers[step % len(workers)]
        step += 1
        handed = hive._take_jobs(worker)
        if not handed and not hive.leases and not hive.pending_jobs:
            break
        for payload in handed:
            job_id = str(payload["id"])
            # every delivery carries a monotone queue-age stamp
            assert payload["queued_s"] >= 0.0
            roll = rng.random()
            if int(job_id.rsplit("-", 1)[1]) % 97 == 0:
                # a pathological cohort that NEVER uploads: every
                # delivery goes silent, so these jobs march through
                # redelivery to abandonment-by-policy and stay there
                clock[0] += hive.lease_s + 0.1
                hive.sweep()
                continue
            if roll < 0.04 and payload["attempt"] < hive.max_attempts:
                # an overload shed: requeued, shedder excluded
                ack = record(error_result(
                    _job(job_id), "shed", kind="overloaded"), worker)
                assert ack["status"] == "requeued"
                refusals += 1
            elif roll < 0.07:
                # worker goes silent on this one: its lease expires
                # (redelivery, or abandonment at max_attempts)...
                clock[0] += hive.lease_s + 0.1
                hive.sweep()
                if roll < 0.055:
                    # ...and then the straggler upload lands anyway:
                    # the first settle wins; if policy had already
                    # abandoned the job, the upload SALVAGES it (one
                    # job must never read as abandoned AND completed)
                    ack = record(_ok_result(job_id, worker), worker)
                    assert ack["status"] in ("ok", "duplicate")
                    late_uploads += 1
            else:
                ack = record(_ok_result(job_id, worker), worker)
                if ack["status"] == "ok" and rng.random() < 0.05:
                    # a racing double upload: acked, never counted
                    dup = record(_ok_result(job_id, "other"), "other")
                    assert dup == {"status": "duplicate"}
                    injected_dupes += 1
        if step > 50_000:  # safety valve: must never loop forever
            raise AssertionError("reconciliation churn did not converge")

    stats = hive.stats()
    issued = [f"scale-{i}" for i in range(n)]
    completed = set(hive.completed)
    abandoned = set(hive.abandoned)
    # exactly once: every job settled XOR abandoned, none twice, none
    # lost — at thousands of jobs with every race injected
    assert completed.isdisjoint(abandoned)
    assert completed | abandoned == set(issued)
    assert len(hive.abandoned) == len(abandoned)  # no double-abandon
    uploaded = hive.uploaded_ids()
    assert len(uploaded) == len(set(uploaded)) == len(completed)
    # the registry snapshot agrees with the lists TO THE JOB
    metrics = stats["metrics"]

    def counter(name: str, label: str = "") -> float:
        return metrics[name]["values"].get(label, 0)

    assert stats["completed"] == len(completed)
    assert set(stats["abandoned"]) == abandoned
    assert counter("chiaswarm_hive_results_completed_total") \
        == len(completed)
    assert counter("chiaswarm_hive_results_duplicate_total") \
        == len(hive.duplicate_results) >= injected_dupes
    # abandonments are monotone events; the LIST shrinks when a
    # straggler upload salvages one — counters reconcile exactly
    assert counter("chiaswarm_hive_jobs_salvaged_total") == salvaged
    assert counter("chiaswarm_hive_jobs_abandoned_total") \
        == len(abandoned) + salvaged
    assert counter("chiaswarm_hive_jobs_redispatched_total",
                   "overloaded") == refusals
    # grants = attempts actually handed out — nothing leaks
    assert counter("chiaswarm_hive_leases_granted_total") \
        == sum(hive.attempts.values())
    assert stats["pending"] == 0 and not stats["leased"]
    assert injected_dupes > 20 and late_uploads > 20 and refusals > 20
    assert salvaged > 0, "the salvage path never exercised"
    assert abandoned, "the abandonment path never exercised"
    if restart:
        # the crash actually happened, the replacement is a REPLAYED
        # hive (epoch bumped, recovery counted), and every assertion
        # above reconciled journal-rebuilt counters with live ones
        assert restarted, "the mid-run hive restart never triggered"
        assert hive.hive_epoch == 2
        assert counter("chiaswarm_hive_recoveries_total") == 1
        assert stats["journal"]["records_written"] > 0


# ---------------------------------------------------------------------------
# fleet chaos: real workers, scripted executors
# ---------------------------------------------------------------------------


def _fleet_worker(uri: str, name: str, executor=None, **over) -> Worker:
    return Worker(settings=fleet_settings(uri, name, **over),
                  pool=[StubSlot(name=name)],
                  registry=ModelRegistry(catalog=[], allow_random=True),
                  executor=executor or ChaoticExecutor())


def test_partitioned_worker_late_upload_races_redelivery_exactly_once():
    """Satellite 3, end to end with real workers: W1 takes the job, gets
    partitioned past its lease, finishes anyway, and keeps retrying the
    upload; the job redelivers to W2 which completes it; the partition
    heals and W1's stale upload lands — exactly one result is acked,
    zero jobs lost, counters agree with the registry snapshot."""

    async def scenario():
        hive = MiniHive(lease_s=0.5, delay_s=0.01, max_jobs_per_poll=1)
        uri = await hive.start()
        hive.submit(_job("race-1", chaos=["slow"]))

        workers = [
            _fleet_worker(uri, f"fleet-{tag}",
                          ChaoticExecutor(slow_s=0.4),
                          upload_retries=40, upload_retry_delay_s=0.05)
            for tag in ("a", "b")
        ]
        tasks = [asyncio.create_task(w.run()) for w in workers]
        try:
            # wait for the lease; partition the holder in the SAME loop
            # tick (no await in between) so it cannot sneak an upload in
            holder = None
            deadline = time.monotonic() + 30
            while holder is None and time.monotonic() < deadline:
                holder = hive.lease_holder("race-1")
                if holder is not None:
                    hive.partition(holder)
                    break
                await asyncio.sleep(0.01)
            assert holder is not None, "job never leased"

            # the redelivered copy must be completed by the OTHER worker
            await hive.wait_for_results(1, timeout=60)
            assert hive.completed["race-1"]["worker_name"] != holder

            # heal: the stale upload lands as an idempotent duplicate
            hive.heal(holder)
            deadline = time.monotonic() + 30
            while not hive.duplicate_results and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.02)
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=20)
                                   for t in tasks),
                                 return_exceptions=True)
            await hive.stop()

        assert hive.uploaded_ids() == ["race-1"]          # exactly once
        assert len(hive.duplicate_results) == 1           # stale, acked
        assert hive.duplicate_results[0]["worker_name"] == holder
        # counters == lists (the satellite's registry-agreement clause)
        snap = hive.stats()
        assert snap["completed"] == 1 and snap["duplicates"] == 1
        assert _counter(hive, "chiaswarm_hive_leases_expired_total") >= 1
        assert _counter(hive, "chiaswarm_hive_jobs_redelivered_total") >= 1

    asyncio.run(scenario())


def test_starvation_valve_redelivery_back_to_self_runs_once():
    """With every OTHER worker excluded, the valve can redeliver a job
    BACK to the worker still running it. The duplicate delivery must be
    dropped worker-side (a second local copy would orphan heartbeat
    coverage of whichever copy outlives the first settle and churn the
    lease forever): the job executes once and settles exactly once."""

    async def scenario():
        hive = MiniHive(lease_s=30.0, delay_s=0.01, max_jobs_per_poll=1)
        uri = await hive.start()
        hive.submit(_job("self-1", chaos=["slow"]))
        executor = ChaoticExecutor(slow_s=1.5)
        worker = _fleet_worker(uri, "fleet-self", executor)
        task = asyncio.create_task(worker.run())
        try:
            deadline = time.monotonic() + 30
            while hive.lease_holder("self-1") is None and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            assert hive.lease_holder("self-1") == "fleet-self"
            # preemption notice mid-run: the lease expires NOW, the only
            # live worker is the (excluded) holder, so the next poll
            # hands the job straight back to it
            hive.expire_worker("fleet-self")
            await hive.wait_for_results(1, timeout=60)
        finally:
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)
            await hive.stop()

        assert hive.uploaded_ids() == ["self-1"]          # exactly once
        assert executor.attempts.get("self-1", 0) == 1    # ONE local run
        assert _counter(hive, "chiaswarm_hive_jobs_redelivered_total") >= 1

    asyncio.run(scenario())


def test_heartbeat_reports_lost_leases_to_worker():
    """Worker side of lease loss: a heartbeat naming a job the hive no
    longer leases to this worker comes back in ``lost`` — counted in
    the worker's ``leases_lost`` stat ONCE per loss, not once per beat
    for as long as the local run continues (local work continues; the
    upload dedupes hive-side)."""

    async def scenario():
        hive = MiniHive(lease_s=30.0, delay_s=0.01)
        uri = await hive.start()
        worker = _fleet_worker(uri, "ghost-worker", heartbeat_s=0.05)
        # an in-flight job the hive never leased to us — the minimal
        # stand-in for "the lease moved on while we were partitioned"
        worker._inflight["ghost-1"] = 0.0
        task = asyncio.create_task(worker.run())
        try:
            deadline = time.monotonic() + 30
            while worker.stats.leases_lost < 1 and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            assert worker.stats.leases_lost == 1
            # the hive keeps reporting the loss every beat while the job
            # stays in flight — it must NOT be re-counted (a 60s local
            # run would otherwise inflate the metric by ~600x)
            beats_before = worker.stats.lease_heartbeats
            while worker.stats.lease_heartbeats < beats_before + 5 and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            assert worker.stats.leases_lost == 1
            # ...but a NEW loss of the same id (job settled locally, then
            # re-leased and lost again) counts as a fresh event. NB: the
            # heartbeat loop skips the POST (and the counter) while
            # nothing is in flight, so wait in wall time, not beats.
            worker._inflight.pop("ghost-1", None)
            await asyncio.sleep(0.3)  # several empty beats: state resets
            worker._inflight["ghost-1"] = 0.0
            while worker.stats.leases_lost < 2 and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.02)
        finally:
            worker._inflight.pop("ghost-1", None)
            worker.request_stop()
            await asyncio.wait_for(task, timeout=20)
            await hive.stop()
        assert worker.stats.lease_heartbeats >= 1
        assert worker.stats.leases_lost == 2

    asyncio.run(scenario())


def test_checkpoint_spool_attached_only_with_heartbeats():
    """With heartbeats off (the reference-hive default) nothing ever
    delivers a checkpoint anywhere — the spool must not be attached to
    slots, so lanes/solo jobs pay no snapshot cost for unread state."""
    registry = ModelRegistry(catalog=[], allow_random=True)
    off = Worker(settings=fleet_settings("http://h", "hb-off",
                                         heartbeat_s=0.0),
                 registry=registry, pool=[StubSlot()])
    assert all(getattr(s, "_checkpoint_spool", None) is None
               for s in off.pool)
    on = Worker(settings=fleet_settings("http://h", "hb-on",
                                        heartbeat_s=0.1),
                registry=registry, pool=[StubSlot()])
    assert all(getattr(s, "_checkpoint_spool", None) is on.checkpoints
               for s in on.pool)


def test_killed_worker_mid_job_loses_nothing():
    """A worker killed outright (task cancelled + partitioned, the
    in-process SIGKILL analog) mid-execution: its leases expire, every
    one of its jobs redelivers, and all jobs in the system settle
    exactly once on the survivors."""

    async def scenario():
        hive = MiniHive(lease_s=0.5, delay_s=0.01, max_jobs_per_poll=2)
        uri = await hive.start()
        jobs = [_job(f"k-{i}", chaos=["slow"]) for i in range(6)]
        for job in jobs:
            hive.submit(job)

        workers = [_fleet_worker(uri, f"kfleet-{tag}",
                                 ChaoticExecutor(slow_s=0.4))
                   for tag in ("a", "b", "c")]
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        victim = None
        victim_jobs: list[str] = []
        try:
            deadline = time.monotonic() + 30
            while victim is None and time.monotonic() < deadline:
                for worker in workers:
                    name = worker.settings.worker_name
                    leased = hive.leased_ids(name)
                    if leased:
                        # partition in the same loop tick as detection:
                        # nothing from the victim lands after this point
                        victim, victim_jobs = name, leased
                        hive.partition(name)
                        break
                if victim is None:
                    await asyncio.sleep(0.01)
            assert victim is not None, "no worker ever took a job"
            tasks[victim].cancel()     # and the process "dies"
            await asyncio.gather(tasks[victim], return_exceptions=True)

            await hive.wait_for_results(len(jobs), timeout=120)
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=20)
                                   for t in tasks.values()),
                                 return_exceptions=True)
            await hive.stop()

        uploaded = hive.uploaded_ids()
        assert sorted(uploaded) == sorted(j["id"] for j in jobs)
        assert len(uploaded) == len(set(uploaded))  # exactly once
        assert hive.abandoned == []
        # the victim's in-flight jobs went through redelivery
        assert victim_jobs
        redelivered = _counter(hive,
                               "chiaswarm_hive_jobs_redelivered_total")
        assert redelivered >= len(victim_jobs)
        for job_id in victim_jobs:
            assert hive.completed[job_id]["worker_name"] != victim

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# THE acceptance gate: kill mid-lane, resume from checkpoint step >= 1
# ---------------------------------------------------------------------------


def test_fleet_worker_kill_mid_lane_resumes_from_checkpoint(monkeypatch):
    """ISSUE 6 acceptance: 3 workers with real lanes on one mini-hive;
    the worker holding a checkpointed job is killed mid-lane. Every
    in-flight job completes exactly once, and the redelivered job
    provably resumes at checkpoint step >= 1 — asserted via the
    result's resume-step stamp (which also rides the job's step span)
    and the survivors' rows_resumed metric — not from step 0."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    # stretch lane wall time so the kill deterministically lands
    # mid-lane (24 steps x 80 ms >> detection latency)
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.08")

    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)

    def lane_job(i: int) -> dict:
        return {"id": f"lane-{i}", "model_name": "tiny",
                "prompt": f"fleet prompt {i}", "seed": 900 + i,
                "num_inference_steps": 24, "guidance_scale": 7.5,
                "height": 64, "width": 64, "content_type": "image/png"}

    async def scenario():
        # a GENEROUS lease: the three workers' first lane compiles are
        # GIL-heavy enough to starve the in-process heartbeat tasks for
        # seconds, and a sub-second lease would expire (and churn every
        # job through redelivery with no checkpoint yet) before step 1
        # even runs. The kill below revokes the victim's leases
        # explicitly via expire_worker — the preemption-notice path —
        # so redelivery is immediate AND deterministic.
        hive = MiniHive(lease_s=60.0, delay_s=0.01, max_jobs_per_poll=1)
        uri = await hive.start()
        for i in range(3):
            hive.submit(lane_job(i))

        workers = []
        for tag in ("a", "b", "c"):
            pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                            devices=jax.devices()[:1])
            workers.append(Worker(
                settings=fleet_settings(uri, f"lanefleet-{tag}",
                                        job_deadline_s=600.0,
                                        heartbeat_s=0.05),
                registry=registry, pool=pool))
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        victim = victim_job = None
        try:
            # wait until some job's checkpoint (step >= 1) reached the
            # hive, then kill its lease holder mid-lane — partitioned in
            # the same loop tick as detection, so the victim cannot
            # finish-and-upload between the check and the kill
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
            # the preemption notice: revoke the dead worker's leases NOW
            # instead of waiting out lease_s — its checkpointed job
            # redelivers (with resume state) on this very sweep
            assert victim_job in hive.expire_worker(victim)

            await hive.wait_for_results(3, timeout=300)
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=60)
                                   for t in tasks.values()),
                                 return_exceptions=True)
            # the killed worker skipped graceful shutdown: retire its
            # lanes explicitly so no driver thread outlives the test
            for worker in workers:
                for slot in worker.pool:
                    stepper = getattr(slot, "_stepper", None)
                    if stepper is not None:
                        stepper.shutdown()
            await hive.stop()
        return hive, workers, victim, victim_job

    hive, workers, victim, victim_job = asyncio.run(scenario())

    # every in-flight job completed exactly once, with a real image
    uploaded = hive.uploaded_ids()
    assert sorted(uploaded) == ["lane-0", "lane-1", "lane-2"]
    assert len(uploaded) == len(set(uploaded))
    for result in hive.results:
        assert result["pipeline_config"].get("error") is None, result
        assert "fatal_error" not in result

    # the redelivered job resumed at checkpoint step >= 1, not step 0:
    # the lane stamps resume_step into the result config (and the same
    # dict rides the job's "step" span as meta)
    resumed = hive.completed[victim_job]
    assert resumed["worker_name"] != victim
    stepper_info = resumed["pipeline_config"].get("stepper") or {}
    assert int(stepper_info.get("resume_step", 0)) >= 1, stepper_info

    # and the survivors' metrics agree
    survivor_stats = [
        slot._stepper.stats()
        for worker in workers
        if worker.settings.worker_name != victim
        for slot in worker.pool
        if getattr(slot, "_stepper", None) is not None
    ]
    assert sum(s.get("rows_resumed", 0) for s in survivor_stats) >= 1
    assert _counter(hive, "chiaswarm_hive_checkpoints_stored_total") >= 1
    assert _counter(hive, "chiaswarm_hive_jobs_redelivered_total") >= 1

    # swarmsight (ISSUE 13): the SAME kill/resume run must leave ONE
    # stitched flight record for the victim job spanning both workers —
    # grant(1, victim) -> checkpoint markers -> redelivery ->
    # grant(2, survivor) -> exactly-once settle, attempt chain gapless
    # (tests/test_flight.py carries the full dedicated gate)
    assert hive.flights.verify(["lane-0", "lane-1", "lane-2"]) == []
    record = hive.flights.get(victim_job)
    events = [e["event"] for e in record["events"]]
    assert events.count("settled") == 1 and "checkpoint" in events
    grants = [e for e in record["events"] if e["event"] == "grant"]
    assert [g["attempt"] for g in grants][:2] == [1, 2]
    assert grants[0]["worker"] == victim
    assert record["settled"]["worker"] != victim
    digests = {a["attempt"]: a["digest"]
               for a in record["attempts"] if a["digest"]}
    assert float(digests[record["settled"]["attempt"]]
                 .get("resume_step") or 0) >= 1


def test_planner_drain_mid_lane_graceful_leave_resumes(monkeypatch):
    """swarmplan scale-down safety (ISSUE 19 satellite, mirroring the
    ISSUE 6 kill gate): the autoscaler retires a worker holding a
    mid-lane checkpointed job via the GRACEFUL path — ``request_stop``
    (finish in-flight, upload, exit) plus ``expire_worker`` lease
    preemption, never partition/cancel. The preempted job redelivers
    WITH its checkpoint to a survivor whose lane resumes at step >= 1,
    while the victim's own drain upload races it — exactly-once
    settlement absorbs whichever copy lands second."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    # stretch lane wall time so the drain decision deterministically
    # lands mid-lane (24 steps x 80 ms >> poll/redeliver latency)
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.08")

    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)

    def lane_job(i: int) -> dict:
        return {"id": f"drain-{i}", "model_name": "tiny",
                "prompt": f"drain prompt {i}", "seed": 700 + i,
                "num_inference_steps": 24, "guidance_scale": 7.5,
                "height": 64, "width": 64, "content_type": "image/png"}

    job_ids = [f"drain-{i}" for i in range(3)]

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
                settings=fleet_settings(uri, f"drainfleet-{tag}",
                                        job_deadline_s=600.0,
                                        heartbeat_s=0.05),
                registry=registry, pool=pool))
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        victim = victim_job = None
        try:
            # wait until some job's lane checkpoint (step >= 1) reached
            # the hive, then drain its lease holder — the planner's
            # scale-down actuation, verbatim (loadgen._drain_auto)
            deadline = time.monotonic() + 240
            while victim is None and time.monotonic() < deadline:
                for job_id, ckpt in list(hive.checkpoints.items()):
                    holder = hive.lease_holder(job_id)
                    if ckpt.get("kind") == "lane" and \
                            int(ckpt.get("step", 0)) >= 1 and \
                            holder is not None:
                        victim_job, victim = job_id, holder
                        break
                if victim is None:
                    await asyncio.sleep(0.02)
            assert victim is not None, \
                f"no lane checkpoint ever reached the hive: {hive.stats()}"
            victim_worker = next(
                w for w in workers
                if w.settings.worker_name == victim)
            victim_worker.request_stop()  # graceful: NOT partitioned,
            # NOT cancelled — its in-flight lane finishes and uploads
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
        return hive, workers, victim, victim_job

    hive, workers, victim, victim_job = asyncio.run(scenario())

    # every job settled exactly once — the victim's graceful upload and
    # the survivor's resumed completion raced, and the settle set
    # arbitrated; nothing was lost or abandoned by the scale-down
    uploaded = hive.uploaded_ids()
    assert sorted(set(uploaded)) == job_ids
    assert len(uploaded) == len(set(uploaded))
    assert hive.abandoned == []
    for result in hive.results:
        assert result["pipeline_config"].get("error") is None, result
        assert "fatal_error" not in result

    # the preemption actually moved the job: a second grant went to a
    # survivor (the victim stays excluded after expire_worker), and a
    # survivor lane admitted the row WITH resume state
    assert _counter(hive, "chiaswarm_hive_checkpoints_stored_total") >= 1
    assert _counter(hive, "chiaswarm_hive_jobs_redelivered_total") >= 1
    record = hive.flights.get(victim_job)
    grants = [e for e in record["events"] if e["event"] == "grant"]
    assert [g["attempt"] for g in grants][:2] == [1, 2]
    assert grants[0]["worker"] == victim
    assert grants[1]["worker"] != victim
    survivor_stats = [
        slot._stepper.stats()
        for worker in workers
        if worker.settings.worker_name != victim
        for slot in worker.pool
        if getattr(slot, "_stepper", None) is not None
    ]
    assert sum(s.get("rows_resumed", 0) for s in survivor_stats) >= 1

    # the flight book agrees end to end: gapless attempt chains, one
    # settle per job (whichever copy won), duplicates acked not counted
    assert hive.flights.verify(job_ids) == []
    events = [e["event"] for e in record["events"]]
    assert events.count("settled") == 1 and "checkpoint" in events


# ---------------------------------------------------------------------------
# nightly fleet soak (satellite 5): seeded kills at scale
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_soak_three_workers_kill_faults():
    """Nightly 3-worker soak: a seeded job mix (CHIASWARM_SOAK_SEED,
    nightly CI passes the run id for replay) over one mini-hive, with a
    seeded worker kill mid-run. Invariant: every issued job settles as
    exactly one acked result — redelivery absorbs the kill, duplicates
    are acked but never counted, nothing is abandoned."""
    import os
    import random

    seed = os.environ.get("CHIASWARM_SOAK_SEED", "fleet-soak-default")
    n_jobs = int(os.environ.get("CHIASWARM_SOAK_JOBS", "45"))
    rng = random.Random(f"fleet-soak:{seed}")

    outcome_scripts = (
        (["ok"], 5),
        (["slow"], 3),
        (["oom", "ok"], 2),
        (["fetch", "ok"], 2),
        (["crash"], 1),
        (["fatal"], 1),
        (["hang"], 1),
    )
    weighted = [s for s, w in outcome_scripts for _ in range(w)]
    jobs = [_job(f"soak-{i}", chaos=list(rng.choice(weighted)))
            for i in range(n_jobs)]
    kill_after = rng.randint(n_jobs // 6, n_jobs // 2)

    async def scenario():
        hive = MiniHive(lease_s=0.8, delay_s=0.01, max_jobs_per_poll=3)
        uri = await hive.start()
        for job in jobs:
            hive.submit(job)
        workers = [_fleet_worker(uri, f"soak-{tag}",
                                 ChaoticExecutor(hang_s=1.0, slow_s=0.1),
                                 job_deadline_s=0.3)
                   for tag in ("a", "b", "c")]
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        victim = None
        try:
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                if victim is None and len(hive.results) >= kill_after:
                    # seeded kill: whichever worker holds a lease when
                    # the threshold passes (deterministic given the
                    # scripts; assignment-agnostic assertions below)
                    for worker in workers:
                        name = worker.settings.worker_name
                        if hive.leased_ids(name):
                            victim = name
                            hive.partition(name)
                            tasks[name].cancel()
                            break
                if len(hive.results) >= n_jobs:
                    break
                await asyncio.sleep(0.05)
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=30)
                                   for t in tasks.values()),
                                 return_exceptions=True)
            await hive.stop()
        return hive, victim

    hive, victim = asyncio.run(scenario())
    uploaded = hive.uploaded_ids()
    issued = [j["id"] for j in jobs]
    assert len(uploaded) == len(set(uploaded)), "double-counted result"
    assert sorted(uploaded) == sorted(issued)
    assert hive.abandoned == []
    if victim is not None:
        assert _counter(hive,
                        "chiaswarm_hive_jobs_redelivered_total") >= 0
    # swarmsight (ISSUE 13 satellite): every settled soak job carries a
    # COMPLETE flight record — no orphan span digests, no attempt gaps
    assert hive.flights.verify(issued) == []


@pytest.mark.slow
def test_fleet_soak_mixed_workload_lanes_kill_resume(monkeypatch):
    """Nightly fleet soak for the ISSUE-7 workloads: txt2img, img2img
    and inpaint jobs ride lanes (default-on) across 3 workers; the
    worker holding a checkpointed IMAGE-workload job is killed mid-lane.
    Every job completes exactly once with its correct mode stamp, and
    the redelivered image-workload job resumes from checkpoint step >= 1
    on its own truncated ladder — the kill/resume coverage for the
    newly lane-eligible workloads."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.08")

    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)

    def mixed_job(i: int, uri: str) -> dict:
        kind = ("txt2img", "img2img", "inpaint")[i % 3]
        job = {"id": f"mix-{i}", "model_name": "tiny",
               "prompt": f"soak prompt {i}", "seed": 950 + i,
               "num_inference_steps": 24, "guidance_scale": 7.5,
               "height": 64, "width": 64, "content_type": "image/png"}
        if kind != "txt2img":
            job["start_image_uri"] = f"{uri}/assets/image.png"
            job["strength"] = 0.6
        if kind == "inpaint":
            job["mask_image_uri"] = f"{uri}/assets/mask.png"
        return job

    async def scenario():
        hive = MiniHive(lease_s=60.0, delay_s=0.01, max_jobs_per_poll=1)
        uri = await hive.start()
        jobs = [mixed_job(i, uri) for i in range(6)]
        for job in jobs:
            hive.submit(job)

        workers = []
        for tag in ("a", "b", "c"):
            pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                            devices=jax.devices()[:1])
            workers.append(Worker(
                settings=fleet_settings(uri, f"mixfleet-{tag}",
                                        job_deadline_s=600.0,
                                        heartbeat_s=0.05),
                registry=registry, pool=pool))
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        victim = victim_job = None
        try:
            # wait for an IMAGE-workload lane checkpoint (img2img rows
            # only checkpoint past their start index), then kill its
            # holder mid-lane with the partition+expire preemption path
            deadline = time.monotonic() + 240
            while victim is None and time.monotonic() < deadline:
                for job_id, ckpt in list(hive.checkpoints.items()):
                    holder = hive.lease_holder(job_id)
                    if ckpt.get("kind") == "lane" and \
                            ckpt.get("workload") in ("img2img",
                                                     "inpaint") and \
                            int(ckpt.get("step", 0)) >= 1 and \
                            holder is not None:
                        victim_job, victim = job_id, holder
                        hive.partition(holder)
                        break
                if victim is None:
                    await asyncio.sleep(0.02)
            assert victim is not None, \
                f"no image-workload lane checkpoint: {hive.stats()}"
            tasks[victim].cancel()
            await asyncio.gather(tasks[victim], return_exceptions=True)
            assert victim_job in hive.expire_worker(victim)

            await hive.wait_for_results(6, timeout=500)
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
        return hive, workers, victim, victim_job, jobs

    hive, workers, victim, victim_job, jobs = asyncio.run(scenario())

    uploaded = hive.uploaded_ids()
    assert sorted(uploaded) == sorted(j["id"] for j in jobs)
    assert len(uploaded) == len(set(uploaded))
    by_id = {j["id"]: j for j in jobs}
    for result in hive.results:
        assert result["pipeline_config"].get("error") is None, result
        assert "fatal_error" not in result
        job = by_id[result["id"]]
        want = ("inpaint" if "mask_image_uri" in job else
                "img2img" if "start_image_uri" in job else "txt2img")
        assert result["pipeline_config"]["mode"] == want, result["id"]

    # the redelivered image-workload job resumed mid-ladder, not from
    # its start index
    resumed = hive.completed[victim_job]
    assert resumed["worker_name"] != victim
    stepper_info = resumed["pipeline_config"].get("stepper") or {}
    assert int(stepper_info.get("resume_step", 0)) >= 1, stepper_info
    # the truncated img2img ladder is preserved through redelivery
    assert resumed["pipeline_config"]["denoise_steps"] <= 24

    survivor_stats = [
        slot._stepper.stats()
        for worker in workers
        if worker.settings.worker_name != victim
        for slot in worker.pool
        if getattr(slot, "_stepper", None) is not None
    ]
    assert sum(s.get("rows_resumed", 0) for s in survivor_stats) >= 1
    admitted_img = sum(s.get("rows_admitted_img2img", 0)
                       + s.get("rows_admitted_inpaint", 0)
                       for s in survivor_stats)
    assert admitted_img >= 1, survivor_stats
    # swarmsight (ISSUE 13 satellite): complete flight records for
    # every settled soak job, incl. the killed-and-resumed one
    assert hive.flights.verify([j["id"] for j in jobs]) == []
    flight = hive.flights.get(victim_job)
    assert flight["settled"]["worker"] != victim
    assert [e["event"] for e in flight["events"]].count("settled") == 1
