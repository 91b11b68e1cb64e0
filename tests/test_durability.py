"""swarmdurable (ISSUE 14): crash-safe hive — journaled queue state,
deterministic recovery replay, worker-side hive-outage ride-through.

Four layers:

- **Journal hygiene units** (no hive): append/commit/replay round
  trips, segment rotation, torn-final-record repair (``.bad`` parked +
  counted), corrupt-mid-log recovery (longest consistent prefix), and
  compaction equivalence — replay(snapshot + tail) == replay(full log).
- **Recovery protocol units** (fake clock, no workers): a recovered
  hive rebuilds queue + lease books + checkpoints + flight records,
  bumps the epoch, redelivers pre-crash leases WITH their journaled
  resume state, dedupes pre-crash settles, salvages pre-epoch uploads
  exactly once, and rejects a stale worker's heartbeat via the epoch
  handshake. Without a journal the wire shape is byte-compatible with
  today (the parity gate).
- **Ride-through fleet chaos** (real Worker + ChaoticExecutor): the
  hive is SIGKILL'd under a live worker — the session flips to OUTAGE,
  in-flight work completes, results spool, and the restarted hive
  (same port, recovered from its journal) receives everything exactly
  once via the LIVE dead-letter replay.
- **THE acceptance gate** (real lanes, slow tier): 3 lane workers, the
  hive SIGKILL'd mid-lane and restarted from its journal — zero job
  loss, exactly-once settlement across epochs, a redelivered job
  provably resumes at step >= 1 from the JOURNALED checkpoint, and one
  stitched flight record spans both hive epochs.

Everything is hermetic (loopback only) and scripted/seeded.
"""

from __future__ import annotations

import asyncio
import json
import shutil
import time

import pytest

from chiaswarm_tpu.node.chaos import ChaoticExecutor
from chiaswarm_tpu.node.executor import error_result
from chiaswarm_tpu.node.hivelog import HIVE_EPOCH_KEY, HiveJournal
from chiaswarm_tpu.node.minihive import (
    MiniHive,
    kill_hive,
    restart_hive,
)
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.resilience import HiveSession
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
        job_deadline_s=5.0,
        transient_retries=1,
        retry_backoff_s=0.01, retry_backoff_cap_s=0.05,
        breaker_threshold=5, breaker_cooldown_s=3600.0,
        poll_busy_s=0.02, poll_idle_s=0.04,
        poll_backoff_base_s=0.02, poll_backoff_cap_s=0.1,
        upload_retries=3, upload_retry_delay_s=0.02,
        drain_timeout_s=5.0, result_drain_timeout_s=5.0,
        install_signal_handlers=False,
        heartbeat_s=0.05,
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


def _ok_result(job_id: str, worker: str = "", epoch=None) -> dict:
    result = {"id": job_id, "artifacts": {}, "nsfw": False,
              "pipeline_config": {"mode": "test"}}
    if worker:
        result["worker_name"] = worker
    if epoch is not None:
        result[HIVE_EPOCH_KEY] = epoch
    return result


def _journal(tmp_path, name="hive", **over) -> HiveJournal:
    over.setdefault("fsync", False)  # logic under test, not the disk
    return HiveJournal(tmp_path / name, **over)


def _hive(journal=None, clock=None, **over) -> MiniHive:
    kwargs = dict(lease_s=5.0, max_attempts=3, max_jobs_per_poll=0)
    kwargs.update(over)
    if clock is not None:
        kwargs["clock"] = clock
    return MiniHive(journal=journal, **kwargs)


# ---------------------------------------------------------------------------
# journal hygiene units
# ---------------------------------------------------------------------------


def test_journal_append_commit_replay_roundtrip(tmp_path):
    journal = _journal(tmp_path)
    assert journal.stored_epoch() == 0
    for i in range(5):
        journal.append("submit", id=f"j{i}", t=float(i))
    assert journal.records_written == 0  # nothing durable pre-commit
    assert journal.commit() == 5
    journal.append("grant", id="j0", t=9.0, attempt=1, worker="w")
    journal.commit()
    journal.close()

    snapshot, records = _journal(tmp_path).replay()
    assert snapshot is None
    assert [r["ev"] for r in records] == ["submit"] * 5 + ["grant"]
    assert [r["seq"] for r in records] == list(range(1, 7))
    assert records[-1]["worker"] == "w"


def test_journal_segment_rotation_spans_replay(tmp_path):
    journal = _journal(tmp_path, segment_bytes=1)  # clamped to 4096
    journal.segment_bytes = 256  # force rotation every few records
    for i in range(40):
        journal.append("submit", id=f"j{i}", t=float(i),
                       job={"id": f"j{i}", "prompt": "x" * 64})
        journal.commit()
    journal.close()
    assert len(journal._segments()) > 1

    _, records = _journal(tmp_path).replay()
    assert [r["seq"] for r in records] == list(range(1, 41))


def test_journal_torn_final_record_parked(tmp_path):
    journal = _journal(tmp_path)
    for i in range(4):
        journal.append("submit", id=f"j{i}", t=float(i))
    journal.commit()
    journal.close()
    # a SIGKILL mid-write tears the final record: no newline, half JSON
    segment = journal._segments()[-1]
    with open(segment, "ab") as fh:
        fh.write(b'{"seq": 5, "ev": "gra')

    reopened = _journal(tmp_path)
    _, records = reopened.replay()
    assert [r["seq"] for r in records] == [1, 2, 3, 4]
    assert reopened.tails_parked == 1
    bad = list(tmp_path.glob("hive/*.bad"))
    assert len(bad) == 1 and b"gra" in bad[0].read_bytes()
    # the repaired journal appends cleanly after the last good record
    reopened.append("submit", id="j9", t=9.0)
    reopened.commit()
    reopened.close()
    fresh = _journal(tmp_path)
    _, records = fresh.replay()
    assert [r["seq"] for r in records] == [1, 2, 3, 4, 5]
    assert fresh.tails_parked == 0  # already repaired last time


def test_journal_corrupt_mid_record_stops_at_prefix(tmp_path):
    journal = _journal(tmp_path)
    for i in range(6):
        journal.append("submit", id=f"j{i}", t=float(i))
    journal.commit()
    journal.close()
    segment = journal._segments()[-1]
    lines = segment.read_bytes().splitlines(keepends=True)
    lines[3] = b'{"seq": 4, "ev": CORRUPT}\n'
    segment.write_bytes(b"".join(lines))

    reopened = _journal(tmp_path)
    _, records = reopened.replay()
    # longest consistent prefix: records 1-3; 4+ parked as .bad
    assert [r["seq"] for r in records] == [1, 2, 3]
    assert reopened.tails_parked == 1
    assert reopened.last_seq == 3
    bad = list(tmp_path.glob("hive/*.bad"))
    assert len(bad) == 1 and b"CORRUPT" in bad[0].read_bytes()


def test_journal_commit_failure_keeps_batch_and_rolls_back(tmp_path):
    """A transient write failure must not drop the batch: the seqs are
    already assigned, so losing them would leave a permanent sequence
    gap every future replay stops at. The failed commit raises (the
    hive never acks), keeps the buffer, rolls the segment back to its
    known-good prefix — and the retry lands gapless."""
    journal = _journal(tmp_path)
    journal.append("submit", id="a", t=0.0)
    journal.commit()
    journal.append("submit", id="b", t=1.0)
    real_fh = journal._fh

    class FailingFH:
        def write(self, data):
            raise OSError(28, "No space left on device")

        def __getattr__(self, name):
            return getattr(real_fh, name)

    journal._fh = FailingFH()
    with pytest.raises(OSError):
        journal.commit()
    journal._fh = real_fh
    assert journal.commit() == 1  # the batch survived; retry succeeds
    journal.close()
    _, records = _journal(tmp_path).replay()
    assert [r["seq"] for r in records] == [1, 2]
    assert [r["id"] for r in records] == ["a", "b"]


def test_constructor_attach_repairs_torn_tail(tmp_path):
    """Attaching a journal via the MiniHive constructor (not recover)
    must run the repairing replay FIRST: appending a new epoch after a
    crash-torn tail would otherwise put every post-attach record behind
    bytes a future recovery parks wholesale."""
    journal = _journal(tmp_path)
    hive = _hive(journal=journal, clock=lambda: 0.0)
    hive.submit(_job("old-0"))
    journal.close()
    segment = journal._segments()[-1]
    with open(segment, "ab") as fh:
        fh.write(b'{"seq": 99, "ev": "gra')  # the SIGKILL tear

    attached = _hive(journal=_journal(tmp_path), clock=lambda: 0.0)
    assert attached.journal.tails_parked == 1  # repaired at attach
    assert attached.hive_epoch == 2
    attached.submit(_job("new-0"))
    attached.journal.close()
    # recovery replays BOTH lives' records — nothing post-attach was
    # parked behind the (already-repaired) tear
    recovered = MiniHive.recover(_journal(tmp_path),
                                 clock=lambda: 0.0)
    pending = {str(j["id"]) for j in recovered.pending_jobs}
    assert "new-0" in pending
    assert recovered.hive_epoch == 3


def test_journal_sequence_gap_detected(tmp_path):
    journal = _journal(tmp_path)
    for i in range(4):
        journal.append("submit", id=f"j{i}", t=float(i))
    journal.commit()
    journal.close()
    segment = journal._segments()[-1]
    lines = segment.read_bytes().splitlines(keepends=True)
    del lines[2]  # silently lose seq 3 — replay must NOT bridge the gap
    segment.write_bytes(b"".join(lines))

    reopened = _journal(tmp_path)
    _, records = reopened.replay()
    assert [r["seq"] for r in records] == [1, 2]
    assert reopened.tails_parked == 1


def _drive_ops(hive, clock) -> list[str]:
    """A deterministic op mix covering every journaled transition:
    settles, redispatch, duplicate, lease expiry, abandonment, and a
    straggler salvage."""
    issued = [f"op-{i}" for i in range(8)]
    for job_id in issued:
        hive.submit(_job(job_id))
    clock[0] += 0.1
    handed = hive._take_jobs("wA")
    assert len(handed) == 8
    # settle 3 normally (one twice: a duplicate ack)
    for job_id in ("op-0", "op-1", "op-2"):
        assert hive._record_result(_ok_result(job_id, "wA"),
                                   "wA")["status"] == "ok"
    assert hive._record_result(_ok_result("op-0", "wB"),
                               "wB")["status"] == "duplicate"
    # redispatch one by error kind
    assert hive._record_result(
        error_result(_job("op-3"), "nope", kind="model_unavailable"),
        "wA")["status"] == "requeued"
    # march op-4..7 through lease expiry to abandonment (max_attempts)
    for _ in range(hive.max_attempts + 1):
        clock[0] += hive.lease_s + 0.1
        hive.sweep()
        hive._take_jobs("wB")
        clock[0] += 0.05
    clock[0] += hive.lease_s + 0.1
    hive.sweep()
    assert hive.abandoned, "abandonment never exercised"
    # a straggler upload salvages one abandoned job
    salvage_id = hive.abandoned[0]
    assert hive._record_result(_ok_result(salvage_id, "wB"),
                               "wB")["status"] == "ok"
    return issued


def test_compaction_equivalence_snapshot_plus_tail(tmp_path):
    """replay(snapshot + tail) must rebuild EXACTLY the state
    replay(full log) does — dump_state to dump_state, counters and
    flight records included."""
    clock = [0.0]
    journal = _journal(tmp_path, "hive", compact_every=0)
    hive = _hive(journal=journal, clock=lambda: clock[0])
    for i in range(4):
        hive.submit(_job(f"pre-{i}"))
    clock[0] += 0.1
    hive._take_jobs("wA")
    hive._record_result(_ok_result("pre-0", "wA"), "wA")
    # snapshot mid-history, KEEPING the covered segments so both replay
    # paths stay available over one identical event stream
    journal.write_snapshot(hive.dump_state(), epoch=hive.hive_epoch,
                           t=clock[0], prune=False)
    # tail ops after the snapshot
    _drive_ops(hive, clock)
    journal.close()

    # twin B: the same journal without its snapshot = the full log
    shutil.copytree(tmp_path / "hive", tmp_path / "hive-full")
    for snap in (tmp_path / "hive-full").glob("snapshot-*.json"):
        snap.unlink()

    recovered_snap = MiniHive.recover(
        _journal(tmp_path, "hive"), lease_s=5.0, max_attempts=3,
        clock=lambda: clock[0])
    recovered_full = MiniHive.recover(
        _journal(tmp_path, "hive-full"), lease_s=5.0, max_attempts=3,
        clock=lambda: clock[0])
    state_snap = recovered_snap.dump_state()
    state_full = recovered_full.dump_state()
    assert state_snap == state_full
    assert recovered_snap.hive_epoch == recovered_full.hive_epoch == 2
    # and both reconcile: the durable counters agree with the lists
    for hive2 in (recovered_snap, recovered_full):
        assert hive2._completed.value() == len(hive2.completed)
        assert hive2._abandoned.value() == \
            len(hive2.abandoned) + hive2._salvaged.value()


def test_compaction_prunes_segments_and_auto_triggers(tmp_path):
    clock = [0.0]
    journal = _journal(tmp_path, compact_every=10)
    hive = _hive(journal=journal, clock=lambda: clock[0])
    for i in range(12):  # > compact_every records via submits + grants
        hive.submit(_job(f"c-{i}"))
    clock[0] += 0.1
    hive._take_jobs("wA")
    assert journal.snapshots_written >= 1
    assert journal.segments_pruned >= 1
    # recovery over the pruned journal still sees everything
    journal.close()
    recovered = MiniHive.recover(_journal(tmp_path), lease_s=5.0,
                                 max_attempts=3,
                                 clock=lambda: clock[0])
    assert len(recovered.leases) + len(recovered.pending_jobs) == 12


# ---------------------------------------------------------------------------
# recovery protocol units (fake clock)
# ---------------------------------------------------------------------------


def test_recover_rebuilds_queue_leases_checkpoints_and_redelivers(
        tmp_path):
    clock = [0.0]
    journal = _journal(tmp_path)
    hive = _hive(journal=journal, clock=lambda: clock[0],
                 max_jobs_per_poll=2)
    assert hive.hive_epoch == 1
    for i in range(4):
        hive.submit(_job(f"r-{i}"))
    clock[0] += 0.1
    handed = hive._take_jobs("w1")
    assert [p[HIVE_EPOCH_KEY] for p in handed] == [1, 1]
    trace_ids = {p["id"]: p["trace_ctx"]["trace_id"] for p in handed}
    # heartbeat checkpoint custody rides the journal (direct append —
    # the HTTP handler unit is covered by the handshake test below)
    hive.checkpoints["r-0"] = {"kind": "lane", "step": 7}
    hive._journal("checkpoint", id="r-0", t=clock[0], worker="w1",
                  checkpoint={"kind": "lane", "step": 7})
    hive._journal_commit()
    assert hive._record_result(_ok_result("r-1", "w1", epoch=1),
                               "w1")["status"] == "ok"
    journal.close()
    # the crash: in-memory hive is garbage; recover from the journal
    recovered = MiniHive.recover(_journal(tmp_path), lease_s=5.0,
                                 max_attempts=3, max_jobs_per_poll=0,
                                 clock=lambda: clock[0])
    assert recovered.hive_epoch == 2
    # settled job deduped across the restart
    assert recovered.completed["r-1"]["recovered"] is True
    assert recovered._record_result(
        _ok_result("r-1", "w1", epoch=1), "w1") == {"status": "duplicate"}
    # pre-crash leases are void: first sweep redelivers r-0 WITH its
    # journaled checkpoint, and the queue copy of r-2/r-3 survives
    clock[0] += 0.01
    handed2 = recovered._take_jobs("w2")
    by_id = {p["id"]: p for p in handed2}
    assert set(by_id) == {"r-0", "r-2", "r-3"}
    assert by_id["r-0"]["attempt"] == 2
    assert by_id["r-0"]["resume"] == {"kind": "lane", "step": 7}
    assert by_id["r-0"][HIVE_EPOCH_KEY] == 2
    # ONE trace spans both epochs, and the story shows the restart
    assert recovered.flights.trace_id_of("r-0") == \
        trace_ids["r-0"]
    record = recovered.flights.get("r-0")
    events = [e["event"] for e in record["events"]]
    assert events[:2] == ["submit", "grant"]
    assert "hive_recovered" in events
    grants = [e for e in record["events"] if e["event"] == "grant"]
    assert [g.get("epoch") for g in grants] == [1, 2]
    assert _counter(recovered,
                    "chiaswarm_hive_recoveries_total") == 1


def _counter(hive, name: str) -> float:
    metric = hive.metrics.get(name)
    return 0.0 if metric is None else metric.value()


def test_pre_epoch_upload_settles_once_as_epoch_salvage(tmp_path):
    clock = [0.0]
    journal = _journal(tmp_path)
    hive = _hive(journal=journal, clock=lambda: clock[0])
    hive.submit(_job("s-0"))
    clock[0] += 0.1
    hive._take_jobs("w1")
    journal.close()
    recovered = MiniHive.recover(_journal(tmp_path), lease_s=5.0,
                                 max_attempts=3,
                                 clock=lambda: clock[0])
    # the worker that rode through the crash uploads its epoch-1 work
    ack = recovered._record_result(_ok_result("s-0", "w1", epoch=1),
                                   "w1")
    assert ack == {"status": "ok"}
    assert _counter(recovered,
                    "chiaswarm_hive_epoch_salvage_total") == 1
    # settled exactly once: the second copy (either epoch) is a dup
    assert recovered._record_result(
        _ok_result("s-0", "w2", epoch=2), "w2") == {"status": "duplicate"}
    assert _counter(recovered,
                    "chiaswarm_hive_epoch_salvage_total") == 1
    record = recovered.flights.get("s-0")
    events = [e["event"] for e in record["events"]]
    assert "epoch_salvage" in events
    assert events.count("settled") == 1
    # the settle stamp names both epochs
    assert record["settled"]["epoch"] == 2


def test_epoch_handshake_rejects_stale_worker(tmp_path):
    """A heartbeat claiming a pre-restart epoch is rejected whole: no
    lease extension, no checkpoint custody, every claimed job reported
    lost, and the current epoch handed back for re-registration."""

    async def scenario():
        clock = [0.0]
        journal = _journal(tmp_path)
        hive = _hive(journal=journal, clock=lambda: clock[0])
        hive.submit(_job("h-0"))
        clock[0] += 0.1
        hive._take_jobs("w1")
        journal.close()
        recovered = MiniHive.recover(_journal(tmp_path), lease_s=5.0,
                                     max_attempts=3,
                                     clock=lambda: clock[0])
        uri = await recovered.start()
        # re-grant h-0 in the new epoch so a live lease exists
        clock[0] += 0.01
        [payload] = recovered._take_jobs("w2")
        assert payload[HIVE_EPOCH_KEY] == 2
        import aiohttp

        async with aiohttp.ClientSession() as session:
            stale_beat = {"worker_name": "w2", HIVE_EPOCH_KEY: 1,
                          "jobs": [{"id": "h-0",
                                    "checkpoint": {"step": 3}}]}
            async with session.post(f"{uri}/api/heartbeat",
                                    json=stale_beat) as response:
                stale_ack = await response.json()
            stale_custody = "h-0" in recovered.checkpoints
            fresh_beat = dict(stale_beat)
            fresh_beat[HIVE_EPOCH_KEY] = 2
            async with session.post(f"{uri}/api/heartbeat",
                                    json=fresh_beat) as response:
                fresh_ack = await response.json()
        await recovered.stop()
        return recovered, stale_ack, stale_custody, fresh_ack

    recovered, stale_ack, stale_custody, fresh_ack = \
        asyncio.run(scenario())
    assert stale_ack["status"] == "stale_epoch"
    assert stale_ack[HIVE_EPOCH_KEY] == 2
    assert stale_ack["lost"] == ["h-0"]
    # the stale beat stored NO custody and extended nothing
    assert stale_custody is False
    assert _counter(recovered,
                    "chiaswarm_hive_stale_epoch_heartbeats_total") == 1
    assert _counter(recovered,
                    "chiaswarm_hive_checkpoints_stale_total") == 1
    # the re-registered beat (current epoch) is served normally
    assert fresh_ack["status"] == "ok"
    assert fresh_ack[HIVE_EPOCH_KEY] == 2
    assert fresh_ack["lost"] == []
    assert recovered.checkpoints["h-0"] == {"step": 3}


def test_wire_parity_without_journal(tmp_path):
    """THE parity gate: a journal-less MiniHive's granted payload keeps
    exactly today's key set — no epoch stamp anywhere on the wire —
    and a journaled hive adds exactly ``hive_epoch``."""
    clock = [0.0]
    plain = _hive(clock=lambda: clock[0])
    job = _job("p-0")
    plain.submit(dict(job))
    clock[0] += 0.1
    [payload] = plain._take_jobs("w1")
    expected = set(job) | {"attempt", "queued_s", "trace_ctx"}
    assert set(payload) == expected
    assert plain.hive_epoch == 0
    # settled results keep their historical shape even when a worker
    # echoes an epoch stamp (defensively popped, never stored)
    ack = plain._record_result(_ok_result("p-0", "w1", epoch=7), "w1")
    assert ack == {"status": "ok"}
    assert HIVE_EPOCH_KEY not in plain.completed["p-0"]
    # flight-record parity: no epoch fields without a journal
    grant = [e for e in plain.flights.get("p-0")["events"]
             if e["event"] == "grant"][0]
    assert "epoch" not in grant

    journaled = _hive(journal=_journal(tmp_path),
                      clock=lambda: clock[0])
    journaled.submit(dict(job))
    clock[0] += 0.1
    [payload2] = journaled._take_jobs("w1")
    assert set(payload2) == expected | {HIVE_EPOCH_KEY}


def test_hive_session_state_machine():
    clock = [0.0]
    session = HiveSession(outage_after=3, clock=lambda: clock[0])
    assert not session.in_outage
    assert session.note_failure("poll") is False
    assert session.note_failure("upload") is False
    assert session.note_failure("poll") is True  # third flips
    assert session.in_outage and session.outages == 1
    assert session.note_failure("poll") is False  # already in outage
    clock[0] += 2.5
    assert session.note_success() is True  # heals exactly once
    assert not session.in_outage
    assert session.note_success() is False
    assert session.last_outage_s == pytest.approx(2.5)
    # a success mid-streak resets the failure ladder
    session.note_failure("poll")
    session.note_failure("poll")
    session.note_success()
    assert session.note_failure("poll") is False
    assert session.consecutive_failures == 1
    snap = session.snapshot()
    assert snap["state"] == "online" and snap["outages"] == 1


# ---------------------------------------------------------------------------
# ride-through fleet chaos (real worker, scripted executor)
# ---------------------------------------------------------------------------


def test_worker_rides_through_hive_kill_and_live_replay(tmp_path):
    """The hive dies under a live worker: the session flips to OUTAGE,
    in-flight work completes and spools, and the restarted hive (same
    port, recovered from its journal) receives every result exactly
    once via the LIVE dead-letter replay — no worker restart."""

    async def scenario():
        journal = _journal(tmp_path)
        hive = MiniHive(lease_s=30.0, delay_s=0.0, max_attempts=4,
                        journal=journal)
        uri = await hive.start()
        port = hive.port
        jobs = [_job(f"ride-{i}", chaos=["slow"]) for i in range(4)]
        for job in jobs:
            hive.submit(job)
        executor = ChaoticExecutor(slow_s=0.4)
        worker = Worker(
            settings=fleet_settings(uri, "rider"),
            pool=[StubSlot(depth=4, name="rider")],
            registry=ModelRegistry(catalog=[], allow_random=True),
            executor=executor)
        task = asyncio.create_task(worker.run())
        try:
            await asyncio.wait_for(executor.started.wait(), timeout=30)
            # SIGKILL the hive mid-everything: in-memory state is gone
            await kill_hive(hive)
            # ride-through: all four jobs complete and spool while the
            # hive is down (uploads fail; the session flips to OUTAGE)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if worker.dead_letters.depth() >= 4 \
                        and not worker._inflight:
                    break
                await asyncio.sleep(0.05)
            assert worker.dead_letters.depth() >= 4, \
                worker.hive_session.snapshot()
            assert worker.hive_session.in_outage
            assert worker.stats.hive_outages >= 1
            # restart from the journal ON THE SAME PORT: the worker
            # heals on its next poll and drains the spool live
            recovered = await restart_hive(journal, port=port,
                                           lease_s=30.0, delay_s=0.0,
                                           max_attempts=4)
            await recovered.wait_for_results(4, timeout=60)
        finally:
            worker.request_stop()
            await asyncio.wait_for(
                asyncio.gather(task, return_exceptions=True), timeout=30)
            await recovered.stop()
        return recovered, worker

    recovered, worker = asyncio.run(scenario())
    uploaded = recovered.uploaded_ids()
    assert sorted(set(uploaded)) == [f"ride-{i}" for i in range(4)]
    assert len(uploaded) == len(set(uploaded))
    assert recovered.hive_epoch == 2
    # the spooled uploads carried their epoch-1 grants: salvage counted
    assert _counter(recovered,
                    "chiaswarm_hive_epoch_salvage_total") >= 1
    # the ride-through signals: an outage, assumed-lost leases, a LIVE
    # replay (distinct from the startup path), and the healed session
    assert worker.stats.hive_outages >= 1
    assert worker.stats.leases_assumed_lost >= 1
    live = worker.metrics.get("chiaswarm_dead_letter_replayed_total")
    assert live.value(when="live") >= 4
    assert live.value(when="startup") == 0
    assert not worker.hive_session.in_outage
    assert worker._last_hive_epoch == 2
    # flight completeness across the epochs
    assert recovered.flights.verify(
        [f"ride-{i}" for i in range(4)]) == []


# ---------------------------------------------------------------------------
# THE acceptance gate: hive SIGKILL'd mid-lane, recovered from journal
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hive_sigkill_mid_lane_recovery_gate(tmp_path, monkeypatch):
    """ISSUE 14 acceptance: 3 real-lane workers on a journaled hive;
    the hive is SIGKILL'd mid-lane (and the worker holding a
    checkpointed job dies in the same incident window), then restarted
    from its journal on the same port. Every job settles exactly once
    across both epochs, the victim's job provably resumes at step >= 1
    from the JOURNALED checkpoint, the survivors ride the outage
    through (work completes, spools, replays live), and one stitched
    flight record spans both hive epochs."""
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
        return {"id": f"dur-{i}", "model_name": "tiny",
                "prompt": f"durable prompt {i}", "seed": 1400 + i,
                "num_inference_steps": 24, "guidance_scale": 7.5,
                "height": 64, "width": 64, "content_type": "image/png"}

    async def scenario():
        journal = _journal(tmp_path)
        hive = MiniHive(lease_s=60.0, delay_s=0.01, max_jobs_per_poll=1,
                        journal=journal)
        uri = await hive.start()
        port = hive.port
        for i in range(3):
            hive.submit(lane_job(i))

        workers = []
        for tag in ("a", "b", "c"):
            pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                            devices=jax.devices()[:1])
            workers.append(Worker(
                settings=fleet_settings(uri, f"durfleet-{tag}",
                                        job_deadline_s=600.0,
                                        drain_timeout_s=30.0,
                                        result_drain_timeout_s=30.0),
                registry=registry, pool=pool))
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        by_name = {w.settings.worker_name: w for w in workers}
        victim = victim_job = None
        recovered = None
        try:
            # wait until a lane checkpoint (step >= 1) is JOURNALED
            # hive-side, then SIGKILL the hive mid-lane; the lease
            # holder of that job dies in the same incident window
            # (combined hive+worker failure), so its job can only come
            # back through journal recovery + redelivery-with-resume
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
                f"no lane checkpoint ever journaled: {hive.stats()}"
            await kill_hive(hive)          # the hive SIGKILL
            tasks[victim].cancel()         # same-incident worker loss
            await asyncio.gather(tasks[victim], return_exceptions=True)

            # the survivors ride through: their lanes run to
            # completion against a dead hive and the results spool
            survivors = [w for w in workers
                         if w.settings.worker_name != victim]
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if all(w.dead_letters.depth() >= 1
                       and not w._inflight for w in survivors):
                    break
                await asyncio.sleep(0.05)
            for w in survivors:
                assert w.dead_letters.depth() >= 1, (
                    w.settings.worker_name, w.hive_session.snapshot())
                assert w.stats.hive_outages >= 1

            # restart from the journal on the SAME port: survivors
            # heal, spools replay live, and the victim's checkpointed
            # job redelivers WITH resume state from the journal
            recovered = await restart_hive(journal, port=port,
                                           lease_s=60.0, delay_s=0.01,
                                           max_jobs_per_poll=1)
            await recovered.wait_for_results(3, timeout=300)
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
            if recovered is not None:
                await recovered.stop()
            else:
                await hive.stop()
        return recovered, workers, by_name, victim, victim_job

    recovered, workers, by_name, victim, victim_job = \
        asyncio.run(scenario())

    # zero job loss, exactly-once settlement across both epochs
    uploaded = recovered.uploaded_ids()
    assert sorted(set(uploaded)) == ["dur-0", "dur-1", "dur-2"]
    assert len(uploaded) == len(set(uploaded))
    assert recovered.abandoned == []
    for result in recovered.results:
        assert result["pipeline_config"].get("error") is None, result
        assert "fatal_error" not in result
        assert HIVE_EPOCH_KEY not in result  # popped before storing
    assert recovered.hive_epoch == 2

    # the victim's job resumed at step >= 1 from the JOURNALED
    # checkpoint — its only possible path: the holder died with the
    # hive, so the resume state crossed the crash through the WAL
    resumed = recovered.completed[victim_job]
    assert resumed["worker_name"] != victim
    stepper_info = resumed["pipeline_config"].get("stepper") or {}
    assert int(stepper_info.get("resume_step", 0)) >= 1, stepper_info
    survivor_stats = [
        slot._stepper.stats()
        for worker in workers
        if worker.settings.worker_name != victim
        for slot in worker.pool
        if getattr(slot, "_stepper", None) is not None
    ]
    assert sum(s.get("rows_resumed", 0) for s in survivor_stats) >= 1

    # ride-through signals: outages counted, spools drained LIVE, and
    # pre-epoch uploads settled exactly once as epoch salvage
    for worker in workers:
        if worker.settings.worker_name == victim:
            continue
        assert worker.stats.hive_outages >= 1
        live = worker.metrics.get(
            "chiaswarm_dead_letter_replayed_total")
        assert live.value(when="live") >= 1
        assert worker._last_hive_epoch == 2
    assert _counter(recovered,
                    "chiaswarm_hive_epoch_salvage_total") >= 1

    # ONE stitched flight record spans both hive epochs: grant 1 in
    # epoch 1 (replayed from the journal), the restart marker, grant 2
    # in epoch 2, exactly one settle — attempt chain gapless
    assert recovered.flights.verify(["dur-0", "dur-1", "dur-2"]) == []
    record = recovered.flights.get(victim_job)
    events = [e["event"] for e in record["events"]]
    assert "hive_recovered" in events and "checkpoint" in events
    assert events.count("settled") == 1
    grants = [e for e in record["events"] if e["event"] == "grant"]
    assert [g["attempt"] for g in grants][:2] == [1, 2]
    assert {g.get("epoch") for g in grants} == {1, 2}
    assert grants[0]["worker"] == victim
    assert record["settled"]["worker"] != victim


# ---------------------------------------------------------------------------
# nightly soak: seeded kill/restart cycles across epochs
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hive_restart_soak_exactly_once_across_epochs(tmp_path):
    """Nightly durability soak (seed = run id): a seeded job mix over a
    journaled hive with TWO mid-run hive kill/restart cycles under 3
    riding-through workers. Every issued job settles exactly once
    across three hive epochs, and every flight record is complete."""
    import os
    import random

    seed = os.environ.get("CHIASWARM_SOAK_SEED", "durable-soak-default")
    n_jobs = int(os.environ.get("CHIASWARM_SOAK_JOBS", "45"))
    rng = random.Random(f"durable-soak:{seed}")
    scripts = ([["ok"]] * 5 + [["slow"]] * 3 + [["oom", "ok"]] * 2
               + [["fetch", "ok"]] * 2 + [["crash"]] + [["fatal"]])
    jobs = [_job(f"soak-{i}", chaos=list(rng.choice(scripts)))
            for i in range(n_jobs)]
    restarts = sorted(rng.sample(range(n_jobs // 5, 4 * n_jobs // 5), 2))

    async def scenario():
        journal = _journal(tmp_path)
        hive = MiniHive(lease_s=2.0, delay_s=0.0, max_attempts=6,
                        max_jobs_per_poll=3, journal=journal)
        uri = await hive.start()
        port = hive.port
        for job in jobs:
            hive.submit(job)
        workers = [Worker(
            settings=fleet_settings(uri, f"dsoak-{tag}",
                                    job_deadline_s=0.5),
            pool=[StubSlot(name=f"dsoak-{tag}")],
            registry=ModelRegistry(catalog=[], allow_random=True),
            executor=ChaoticExecutor(hang_s=1.0, slow_s=0.1))
            for tag in ("a", "b", "c")]
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        cycles = 0
        try:
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                settled = len(hive.completed) + len(hive.abandoned)
                if cycles < len(restarts) and \
                        settled >= restarts[cycles]:
                    # the seeded kill/restart cycle: SIGKILL, then
                    # recover from the journal on the same port
                    await kill_hive(hive)
                    await asyncio.sleep(0.3)  # let outages flip
                    hive = await restart_hive(
                        journal, port=port, lease_s=2.0, delay_s=0.0,
                        max_attempts=6, max_jobs_per_poll=3)
                    cycles += 1
                if len(hive.completed) + len(hive.abandoned) >= n_jobs:
                    break
                hive.sweep()
                await asyncio.sleep(0.05)
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=30)
                                   for t in tasks.values()),
                                 return_exceptions=True)
            await hive.stop()
        return hive, cycles

    hive, cycles = asyncio.run(scenario())
    assert cycles == 2 and hive.hive_epoch == 3
    issued = [j["id"] for j in jobs]
    completed = set(hive.completed)
    abandoned = set(hive.abandoned)
    assert completed.isdisjoint(abandoned)
    assert completed | abandoned == set(issued), \
        sorted(set(issued) - completed - abandoned)
    uploaded = hive.uploaded_ids()
    assert len(uploaded) == len(set(uploaded))
    # flight completeness across ALL epochs (the chaos-soak.yml gate)
    assert hive.flights.verify(issued, require_settled=False) == []
    assert hive.flights.verify(sorted(completed)) == []
    # the journal kept every transition durable across the cycles
    assert hive.journal.snapshot_counters()["records_written"] > 0


# ---------------------------------------------------------------------------
# journal knobs
# ---------------------------------------------------------------------------


def test_journal_env_knobs(tmp_path, monkeypatch):
    monkeypatch.setenv("CHIASWARM_HIVE_JOURNAL_SEGMENT_BYTES", "8192")
    monkeypatch.setenv("CHIASWARM_HIVE_JOURNAL_FSYNC", "0")
    monkeypatch.setenv("CHIASWARM_HIVE_JOURNAL_COMPACT_EVERY", "77")
    journal = HiveJournal(tmp_path / "env")
    assert journal.segment_bytes == 8192
    assert journal.fsync is False
    assert journal.compact_every == 77
    # explicit args beat the environment
    explicit = HiveJournal(tmp_path / "env2", segment_bytes=65536,
                           fsync=True, compact_every=0)
    assert explicit.segment_bytes == 65536
    assert explicit.fsync is True
    assert explicit.compact_every == 0


def test_epoch_sidecar_survives_compaction(tmp_path):
    clock = [0.0]
    journal = _journal(tmp_path)
    hive = _hive(journal=journal, clock=lambda: clock[0])
    hive.submit(_job("e-0"))
    hive.compact()  # epoch records pruned into the snapshot
    journal.close()
    assert _journal(tmp_path).stored_epoch() == 1
    recovered = MiniHive.recover(_journal(tmp_path),
                                 clock=lambda: clock[0])
    assert recovered.hive_epoch == 2
    recovered.journal.close()
    # a second recovery keeps climbing — epochs are monotone forever
    again = MiniHive.recover(_journal(tmp_path),
                             clock=lambda: clock[0])
    assert again.hive_epoch == 3
