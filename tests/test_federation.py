"""swarmfed (ISSUE 17): the federated hive — sharded control plane.

Units pin the contracts the federation rides on:

- **Hash stability**: the job-space partition is a pure function of
  (job id, H) built on sha256 — identical in-process, across a process
  restart (Python's salted ``hash()`` would re-partition every boot),
  and across shard recoveries.
- **Owner-journaled steals**: a cross-shard steal grant is the OWNER's
  journaled state transition; recovery replay rebuilds the steal books
  (counter + flight marker) identically, so ``/api/stats`` reconciles
  across restarts.
- **Per-shard blast radius**: killing one shard degrades only its own
  traffic — the multiplexed worker's OTHER sessions keep serving.
- **Wrong-shard uploads**: forwarded through the router to the owner,
  whose settle set stays the single exactly-once arbiter (a duplicate
  is acked ``duplicate`` there, never double-settled anywhere).
- **Wire parity**: H=1 (and un-federated ShardHive) grants carry
  exactly the PR-14 key set — no ``hive_shard`` stamp anywhere.

THE acceptance gate (slow): 3 shards + 3 real-lane workers, one shard
SIGKILL'd mid-lane and recovered from its own journal — zero job loss,
exactly-once settlement fleet-wide across the epoch bump, the victim
shard's in-flight job resumes at step >= 1 on a survivor, >= 1
cross-shard steal in ``/api/stats``, and one stitched flight record
spanning the steal and both epochs.

Nightly seeded soak (slow; replay with
``CHIASWARM_SOAK_SEED=<run id> pytest tests/test_federation.py --slow
-k soak``): shard-SIGKILL/restart cycles under churn, flight
completeness fleet-wide.
"""

from __future__ import annotations

import asyncio
import subprocess
import sys
import time

import pytest

from chiaswarm_tpu.node.chaos import ChaoticExecutor
from chiaswarm_tpu.node.federation import (
    HIVE_SHARD_KEY,
    FederatedHive,
    ShardHive,
    ShardRouter,
    shard_of,
)
from chiaswarm_tpu.node.hivelog import HIVE_EPOCH_KEY
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.settings import Settings
from chiaswarm_tpu.node.worker import Worker


@pytest.fixture(autouse=True)
def _tmp_root(tmp_path, monkeypatch):
    monkeypatch.setenv("SWARM_TPU_ROOT", str(tmp_path))
    return tmp_path


class StubSlot:
    """Executor-less slot (the test_chaos/test_durability stand-in)."""

    def __init__(self, depth: int = 4, data_width: int = 1,
                 name: str = "stub"):
        self.depth = depth
        self.data_width = data_width
        self.name = name

    def descriptor(self):
        return self.name

    def __call__(self, callback, **kwargs):
        model_name = kwargs.pop("model_name", None)
        seed = int(kwargs.pop("seed", None) or 0)
        artifacts, config = callback(self, model_name, seed=seed,
                                     **kwargs)
        config = dict(config)
        config["seed"] = seed
        return artifacts, config


def fed_settings(uri: str, name: str, **over) -> Settings:
    """Worker settings dialing a federation: ``uri`` is the
    comma-joined shard list (FederatedHive.worker_uri), which
    Settings.hive_uris parses back into one session per shard."""
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


def _ok_result(job_id: str, worker: str = "", shard=None) -> dict:
    result = {"id": job_id, "artifacts": {}, "nsfw": False,
              "pipeline_config": {"mode": "test"}}
    if worker:
        result["worker_name"] = worker
    if shard is not None:
        result[HIVE_SHARD_KEY] = shard
    return result


def _worker(settings: Settings, **over) -> Worker:
    kwargs = dict(pool=[StubSlot(name=settings.worker_name)],
                  registry=ModelRegistry(catalog=[], allow_random=True),
                  executor=ChaoticExecutor())
    kwargs.update(over)
    return Worker(settings=settings, **kwargs)


# ids pre-sorted by their 3-shard owner (golden against sha256; the
# stability test below pins the function itself)
OWNED_BY = {
    0: ["fed-0", "fed-9", "fed-11", "fed-17", "fed-20", "fed-21"],
    1: ["fed-3", "fed-4", "fed-5", "fed-12", "fed-13", "fed-29"],
    2: ["fed-1", "fed-2", "fed-6", "fed-7", "fed-8", "fed-10"],
}


# ---------------------------------------------------------------------------
# hash routing
# ---------------------------------------------------------------------------


def test_shard_of_stable_golden_and_balanced():
    # golden pins: these values are sha256 facts, not implementation
    # accidents — a change here re-partitions every deployed job space
    assert shard_of("load-7", 3) == 1
    assert shard_of("dur-0", 3) == 0
    assert shard_of("42", 5) == 2
    for index, ids in OWNED_BY.items():
        for job_id in ids:
            assert shard_of(job_id, 3) == index
    # H<=1 degenerates to the single hive
    assert shard_of("anything", 1) == 0
    assert shard_of("anything", 0) == 0
    # no shard starves under a uniform id sweep
    counts = [0, 0, 0]
    for i in range(600):
        counts[shard_of(f"bal-{i}", 3)] += 1
    assert min(counts) > 100, counts
    router = ShardRouter(3)
    assert router.owner_index("dur-0") == shard_of("dur-0", 3)


def test_shard_of_stable_across_process_restart():
    """The property ``hash()`` would break: a FRESH interpreter (new
    hash salt) computes the identical partition."""
    ids = [job_id for ids in OWNED_BY.values() for job_id in ids]
    script = (
        "from chiaswarm_tpu.node.federation import shard_of\n"
        f"print([shard_of(j, 3) for j in {ids!r}])\n")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, check=True)
    assert eval(out.stdout.strip()) == [shard_of(j, 3) for j in ids]


# ---------------------------------------------------------------------------
# wire parity (the PR-14 contract, extended per ISSUE 17)
# ---------------------------------------------------------------------------


def test_wire_parity_h1_and_unfederated():
    """H=1 federation and un-federated ShardHive grant exactly the
    PR-14 key set: no ``hive_shard`` stamp, no epoch without a journal
    (the test_durability parity gate, extended across the federation
    seam)."""
    job = _job("p-0")
    expected = set(job) | {"attempt", "queued_s", "trace_ctx"}

    # un-federated ShardHive is a plain MiniHive on the wire
    solo = ShardHive(lease_s=5.0, delay_s=0.0, shard_index=0)
    solo.submit(dict(job))
    [payload] = solo._take_jobs("w1")
    assert set(payload) == expected
    ack = solo._record_result(_ok_result("p-0", "w1", shard=7), "w1")
    assert ack == {"status": "ok"}
    assert HIVE_SHARD_KEY not in solo.completed["p-0"]

    # H=1 federation: same contract end to end
    fed = FederatedHive(n_shards=1, lease_s=5.0, delay_s=0.0)
    fed.submit(dict(job))
    [payload] = fed.shards[0]._take_jobs("w1")
    assert set(payload) == expected


def test_wire_parity_federated_adds_exactly_shard_key(tmp_path):
    job = _job("fed-0")  # owned by shard 0 of 3
    expected = set(job) | {"attempt", "queued_s", "trace_ctx"}

    # journal OFF: federated grants add exactly the shard stamp
    fed = FederatedHive(n_shards=3, lease_s=5.0, delay_s=0.0)
    assert fed.submit(dict(job)) == 0
    [payload] = fed.shards[0]._take_jobs("w1")
    assert set(payload) == expected | {HIVE_SHARD_KEY}
    assert payload[HIVE_SHARD_KEY] == 0

    # journal ON: shard stamp + epoch stamp, nothing else
    fedj = FederatedHive(n_shards=3, journal_root=tmp_path / "hive",
                         journal_fsync=False, lease_s=5.0, delay_s=0.0)
    fedj.submit(dict(job))
    [payload] = fedj.shards[0]._take_jobs("w1")
    assert set(payload) == expected | {HIVE_SHARD_KEY, HIVE_EPOCH_KEY}


def test_api_shards_bootstraps_worker_from_one_front_address():
    """swarmplan satellite (ISSUE 19, PR-17 residue): the front is an
    aggregation plane, not a proxy — workers must dial the shards
    directly. ``GET /api/shards`` closes the bootstrap gap: a worker
    configured with ONE ``hive_front_uri`` resolves the live shard
    list at startup and rebuilds its session bundles from it,
    replacing any stale hand-configured list."""
    import aiohttp

    from chiaswarm_tpu.node.federation import bootstrap_shard_uris

    async def scenario():
        fed = FederatedHive(n_shards=3, lease_s=30.0)
        front = await fed.start()
        try:
            uris = await bootstrap_shard_uris(front)
            assert list(uris) == fed.shard_uris() and len(uris) == 3
            async with aiohttp.ClientSession() as session:
                async with session.get(front + "/api/shards") as resp:
                    assert resp.status == 200
                    body = await resp.json()
            assert body["n_shards"] == 3
            assert body["shards"] == fed.shard_uris()
            assert body["worker_uri"] == fed.worker_uri()

            # a worker knowing only the front (its configured hive_uri
            # is a stale guess) comes up multiplexing every shard
            worker = _worker(fed_settings("http://127.0.0.1:9",
                                          "boot-w0",
                                          hive_front_uri=front))
            await worker._bootstrap_from_front()
            assert worker.settings.hive_shard_uris == uris
            assert worker.settings.hive_uris() == list(uris)
            assert len(worker.shards) == 3

            # an injected hive client is the chaos/test seam and must
            # always win over the bootstrap
            class _Stub:
                pass

            pinned = _worker(fed_settings("http://127.0.0.1:9",
                                          "boot-w1",
                                          hive_front_uri=front),
                             hive=_Stub())
            await pinned._bootstrap_from_front()
            assert len(pinned.shards) == 1
        finally:
            await fed.stop()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# stealing + wrong-shard uploads (direct seam units, no HTTP)
# ---------------------------------------------------------------------------


def test_steal_routes_deepest_peer_and_owner_keeps_books():
    fed = FederatedHive(n_shards=3, lease_s=5.0, delay_s=0.0)
    for job_id in OWNED_BY[1][:1]:
        fed.submit(_job(job_id))
    for job_id in OWNED_BY[2][:3]:  # shard 2 is the deepest peer
        fed.submit(_job(job_id))
    # a poll on EMPTY shard 0 steals exactly one job from shard 2
    [payload] = fed.shards[0]._take_jobs("w1")
    stolen_id = str(payload["id"])
    assert payload[HIVE_SHARD_KEY] == 2
    assert stolen_id in OWNED_BY[2]
    # the lease lives on the OWNER; the thief holds nothing
    assert fed.shards[2].lease_holder(stolen_id) == "w1"
    assert fed.shards[0].leased_ids("w1") == []
    # the steal books: owner's counter + owner's flight marker
    assert fed.shards[2]._steals.value(**{"from": "2", "to": "0"}) == 1
    events = [e["event"] for e in
              fed.shards[2].flights.get(stolen_id)["events"]]
    assert "stolen" in events
    # settle through the owner: exactly-once, fleet-wide
    ack = fed.shards[2]._record_result(
        _ok_result(stolen_id, "w1", shard=2), "w1")
    assert ack == {"status": "ok"}
    assert fed.stats()["aggregate"]["steals"] == {"2->0": 1.0}


def test_steal_skips_shard_partitioned_from_worker():
    fed = FederatedHive(n_shards=3, lease_s=5.0, delay_s=0.0)
    for job_id in OWNED_BY[2][:2]:
        fed.submit(_job(job_id))
    # the only backlogged peer cannot reach this worker: no steal (the
    # lease would live on a hive the worker cannot upload to)
    fed.shards[2].partition("w1")
    assert fed.shards[0]._take_jobs("w1") == []
    # a different worker still steals
    [payload] = fed.shards[0]._take_jobs("w2")
    assert payload[HIVE_SHARD_KEY] == 2


def test_steal_disabled_leaves_empty_polls_empty():
    fed = FederatedHive(n_shards=2, steal=False, lease_s=5.0,
                        delay_s=0.0)
    fed.submit(_job(OWNED_BY[1][0]))
    assert fed.shards[0]._take_jobs("w1") == []
    assert len(fed.shards[1].pending_jobs) == 1


def test_wrong_shard_duplicate_upload_acked_duplicate_not_resettled():
    """ISSUE 17 satellite: an upload duplicated to the WRONG shard is
    forwarded to the owner and acked ``duplicate`` — never
    double-settled on any shard."""
    fed = FederatedHive(n_shards=3, lease_s=5.0, delay_s=0.0)
    job_id = OWNED_BY[1][0]
    fed.submit(_job(job_id))
    [payload] = fed.shards[1]._take_jobs("w1")
    # first settle lands on the owner (normal path)
    ack = fed.shards[1]._record_result(
        _ok_result(job_id, "w1", shard=1), "w1")
    assert ack == {"status": "ok"}
    # the retry lands on the WRONG shard: forwarded, acked duplicate
    ack = fed.shards[0]._record_result(
        _ok_result(job_id, "w1", shard=1), "w1")
    assert ack["status"] == "duplicate"
    aggregate = fed.stats()["aggregate"]
    assert aggregate["completed"] == 1
    assert aggregate["duplicates"] == 1
    assert aggregate["forwarded_uploads"] == 1
    assert len(fed.uploaded_ids()) == 1
    # the duplicate book lives on the owner, not the mis-routed shard
    assert len(fed.shards[1].duplicate_results) == 1
    assert fed.shards[0].duplicate_results == []
    # the stored result never carries routing metadata
    assert HIVE_SHARD_KEY not in fed.completed[job_id]


# ---------------------------------------------------------------------------
# owner-journaled steal: recovery replay reconciles
# ---------------------------------------------------------------------------


def test_steal_grant_journaled_by_owner_replay_reconciles(tmp_path):
    """The steal is the owner's journaled transition: SIGKILL the owner
    shard and recover it from ITS journal — the steal counter, the
    flight marker, and the stolen job's lease all come back; the
    worker's settle (carrying the epoch-1 grant) salvages on the
    recovered epoch-2 shard exactly once."""

    async def scenario():
        fed = FederatedHive(n_shards=2, journal_root=tmp_path / "hive",
                            journal_fsync=False, lease_s=30.0,
                            delay_s=0.0)
        await fed.start()
        victim_id = None
        try:
            for job_id in ("fed-0", "fed-10"):  # shard 0 of 2 owns both
                fed.submit(_job(job_id))
            # steal via an empty poll on shard 1
            [payload] = fed.shards[1]._take_jobs("w1")
            victim_id = str(payload["id"])
            assert payload[HIVE_SHARD_KEY] == 0
            assert fed.shards[0]._steals.value(
                **{"from": "0", "to": "1"}) == 1

            await fed.kill_shard(0)
            recovered = await fed.restart_shard(0)
            # replay rebuilt the steal books identically
            assert recovered._steals.value(
                **{"from": "0", "to": "1"}) == 1
            events = [e["event"] for e in
                      recovered.flights.get(victim_id)["events"]]
            assert "stolen" in events
            assert recovered.hive_epoch == 2
            # the stolen job's lease survived recovery on the OWNER
            assert recovered.lease_holder(victim_id) == "w1"
            # the settle (epoch-1 grant echo) salvages exactly once
            ack = recovered._record_result(
                _ok_result(victim_id, "w1", shard=0), "w1")
            assert ack == {"status": "ok"}
            ack = recovered._record_result(
                _ok_result(victim_id, "w1", shard=0), "w1")
            assert ack["status"] == "duplicate"
            assert fed.stats()["aggregate"]["steals"] == {"0->1": 1.0}
        finally:
            await fed.stop()
        return fed, victim_id

    fed, victim_id = asyncio.run(scenario())
    assert fed.uploaded_ids() == [victim_id]


def test_no_steal_from_a_killed_shard(tmp_path):
    """A killed shard's memory is garbage and its journal detached: an
    empty poll on a live peer must not be granted the dead shard's
    backlog (no journal would hold that grant, and the recovered shard
    would file the job's upload as an orphan digest). Recovered, the
    shard is a steal victim again and the grant is in ITS journal."""

    async def scenario():
        fed = FederatedHive(n_shards=2, journal_root=tmp_path / "hive",
                            journal_fsync=False, lease_s=30.0,
                            delay_s=0.0)
        await fed.start()
        try:
            fed.submit(_job("fed-0"))  # shard 0 of 2 owns it
            await fed.kill_shard(0)
            assert fed.shards[1]._take_jobs("w1") == []
            recovered = await fed.restart_shard(0)
            [payload] = fed.shards[1]._take_jobs("w1")
            assert payload["id"] == "fed-0"
            assert payload[HIVE_SHARD_KEY] == 0
            ack = recovered._record_result(
                dict(_ok_result("fed-0", "w1", shard=0),
                     span_digest={"attempt": payload["attempt"]}), "w1")
            assert ack == {"status": "ok"}
        finally:
            await fed.stop()
        return fed

    fed = asyncio.run(scenario())
    assert fed.verify_flights(["fed-0"]) == []


# ---------------------------------------------------------------------------
# per-shard outage independence (the blast-radius contract)
# ---------------------------------------------------------------------------


def test_shard_outage_degrades_only_its_own_traffic(tmp_path):
    """Kill shard 1 of 3 under a live multiplexed worker: sessions to
    shards 0/2 stay online and their jobs keep settling; only shard
    1's session rides an outage. Restarting shard 1 from its journal
    heals the session and recovers its jobs — fleet-wide exactly-once."""

    async def scenario():
        fed = FederatedHive(n_shards=3, journal_root=tmp_path / "hive",
                            journal_fsync=False, lease_s=30.0,
                            delay_s=0.0)
        await fed.start()
        issued = (OWNED_BY[0][:2] + OWNED_BY[1][:2] + OWNED_BY[2][:2])
        worker = _worker(fed_settings(fed.worker_uri(), "fedrider",
                                      hive_outage_after=2))
        task = asyncio.create_task(worker.run())
        try:
            for job_id in OWNED_BY[1][:2]:
                fed.submit(_job(job_id))
            await fed.kill_shard(1)

            # shards 0/2 keep settling while shard 1 is down
            for job_id in OWNED_BY[0][:2] + OWNED_BY[2][:2]:
                fed.submit(_job(job_id))
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if len(fed.completed) >= 4 \
                        and worker.shards[1].session.in_outage:
                    break
                await asyncio.sleep(0.05)
            assert len(fed.completed) >= 4, fed.stats()["aggregate"]
            assert worker.shards[1].session.in_outage
            assert not worker.shards[0].session.in_outage
            assert not worker.shards[2].session.in_outage
            # the per-shard health surface names the sick session
            states = {b["shard"]: b["session"]["state"]
                      for b in worker.health()["hive_shards"]}
            assert states == {0: "online", 1: "outage", 2: "online"}

            # recovery: shard 1's journal redelivers its jobs; the
            # worker's shard-1 session heals on its next poll
            await fed.restart_shard(1)
            await fed.wait_for_results(6, timeout=60)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if not worker.shards[1].session.in_outage:
                    break
                await asyncio.sleep(0.05)
            assert not worker.shards[1].session.in_outage
        finally:
            worker.request_stop()
            await asyncio.wait_for(
                asyncio.gather(task, return_exceptions=True), timeout=30)
            await fed.stop()
        return fed, worker, issued

    fed, worker, issued = asyncio.run(scenario())
    uploaded = fed.uploaded_ids()
    assert sorted(uploaded) == sorted(issued)
    assert len(uploaded) == len(set(uploaded))
    assert fed.abandoned == []
    assert fed.verify_flights(issued) == []
    # only the killed shard bumped its epoch
    assert fed.stats()["aggregate"]["epochs"] == [1, 2, 1]
    # a multiplexed worker counts ONCE in the merged /api/fleet view
    fleet = fed.fleet_snapshot()
    assert list(fleet["workers"]) == ["fedrider"]


# ---------------------------------------------------------------------------
# THE acceptance gate (slow): shard SIGKILL mid-lane, fleet-wide
# exactly-once across the epoch bump, steal + stitched flight
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_federated_shard_sigkill_mid_lane_recovery_gate(tmp_path,
                                                        monkeypatch):
    """ISSUE 17 acceptance: 3 hive shards + 3 real-lane workers under
    mixed-workload churn; the shard owning every gate job is SIGKILL'd
    mid-lane (and the worker holding a checkpointed job dies in the
    same incident window), then recovered from its own journal. Zero
    job loss; exactly-once settlement FLEET-WIDE across the epoch
    bump; the victim shard's in-flight job resumes at step >= 1 on a
    survivor; >= 1 cross-shard steal reconciles in /api/stats; and one
    stitched flight record spans the steal and both epochs."""
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.mesh import MeshSpec

    monkeypatch.setenv("CHIASWARM_STEPPER", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_CKPT_EVERY", "1")
    monkeypatch.setenv("CHIASWARM_STEPPER_STEP_DELAY_S", "0.08")

    registry = ModelRegistry(
        catalog=[{"name": "tiny", "family": "tiny", "parameters": {}}],
        allow_random=True)

    def lane_job(job_id: str, i: int) -> dict:
        return {"id": job_id, "model_name": "tiny",
                "prompt": f"federated prompt {i}", "seed": 1700 + i,
                "num_inference_steps": 24, "guidance_scale": 7.5,
                "height": 64, "width": 64, "content_type": "image/png"}

    # every gate job is owned by shard 0: polls landing on (empty)
    # shards 1/2 MUST steal, and shard 0 is the in-flight victim
    gate_ids = OWNED_BY[0][:4]

    async def scenario():
        fed = FederatedHive(n_shards=3, journal_root=tmp_path / "hive",
                            journal_fsync=False, lease_s=60.0,
                            delay_s=0.01, max_jobs_per_poll=1)
        await fed.start()
        wuri = fed.worker_uri()
        workers = []
        for tag in ("a", "b", "c"):
            pool = ChipPool(n_slots=1, mesh_spec=MeshSpec({"data": 1}),
                            devices=jax.devices()[:1])
            workers.append(Worker(
                settings=fed_settings(wuri, f"fedfleet-{tag}",
                                      job_deadline_s=600.0,
                                      drain_timeout_s=30.0,
                                      result_drain_timeout_s=30.0),
                registry=registry, pool=pool))
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        for i, job_id in enumerate(gate_ids):
            fed.submit(lane_job(job_id, i))

        shard0 = fed.shards[0]
        victim = victim_job = None
        recovered = None
        try:
            # wait for a lane checkpoint (step >= 1) journaled on
            # shard 0, PREFERRING a stolen job — then SIGKILL the
            # shard mid-lane; the lease holder dies in the same
            # incident window, so its job can only come back through
            # shard-0 journal recovery + redelivery-with-resume
            deadline = time.monotonic() + 240
            fallback_at = time.monotonic() + 120
            while victim is None and time.monotonic() < deadline:
                candidates = []
                for job_id, ckpt in list(shard0.checkpoints.items()):
                    holder = shard0.lease_holder(job_id)
                    if ckpt.get("kind") == "lane" and \
                            int(ckpt.get("step", 0)) >= 1 and \
                            holder is not None:
                        record = shard0.flights.get(job_id) or {}
                        stolen = any(e["event"] == "stolen"
                                     for e in record.get("events", []))
                        candidates.append((stolen, job_id, holder))
                stolen_first = sorted(candidates, reverse=True)
                if stolen_first and (stolen_first[0][0]
                                     or time.monotonic() > fallback_at):
                    _, victim_job, victim = stolen_first[0]
                    break
                await asyncio.sleep(0.02)
            assert victim is not None, \
                f"no lane checkpoint ever journaled: {shard0.stats()}"
            # the survivors' unfinished leases at the incident moment:
            # every one of them MUST come back out of a dead-letter
            # spool (their uploads can only reach the dead owner)
            survivors = [w for w in workers
                         if w.settings.worker_name != victim]
            survivor_leases = {
                w.settings.worker_name:
                    shard0.leased_ids(w.settings.worker_name)
                for w in survivors}
            dead0 = shard0  # in-memory corpse: settle set freezes here
            await fed.kill_shard(0)       # the shard SIGKILL
            tasks[victim].cancel()        # same-incident worker loss
            await asyncio.gather(tasks[victim], return_exceptions=True)

            # survivors ride through: every upload routes to the dead
            # OWNER shard, so finished lanes spool while shards 1/2
            # keep answering their polls (no fleet-wide outage). A
            # settle can land in the kill window, so the expectation
            # re-filters against the corpse's (frozen) settle set.
            def expected_spooled() -> int:
                return sum(
                    1 for name, leased in survivor_leases.items()
                    for job_id in leased
                    if job_id not in dead0.completed)

            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                total = sum(w.shards[0].spool.depth()
                            for w in survivors)
                if total >= expected_spooled() \
                        and all(not w._inflight for w in survivors) \
                        and all(w.shards[0].session.in_outage
                                for w in survivors):
                    break
                await asyncio.sleep(0.05)
            spooled_total = sum(w.shards[0].spool.depth()
                                for w in survivors)
            assert spooled_total >= expected_spooled(), (
                survivor_leases,
                [w.shards[0].session.snapshot() for w in survivors])
            for w in survivors:
                # the dead shard's session rides an outage...
                assert w.shards[0].session.in_outage, \
                    w.shards[0].session.snapshot()
                # ...while the blast radius held: the OTHERS are fine
                assert not w.shards[1].session.in_outage
                assert not w.shards[2].session.in_outage

            # recover shard 0 from ITS OWN journal on its old port:
            # survivors heal, spools replay live, and the victim's
            # checkpointed job redelivers WITH resume state
            recovered = await fed.restart_shard(0)
            await fed.wait_for_results(len(gate_ids), timeout=300)
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
            await fed.stop()
        return fed, recovered, workers, victim, victim_job, spooled_total

    fed, recovered, workers, victim, victim_job, spooled_total = \
        asyncio.run(scenario())

    # zero job loss, exactly-once settlement FLEET-WIDE across epochs
    uploaded = fed.uploaded_ids()
    assert sorted(set(uploaded)) == sorted(gate_ids)
    assert len(uploaded) == len(set(uploaded))
    assert fed.abandoned == []
    for result in fed.results:
        assert result["pipeline_config"].get("error") is None, result
        assert "fatal_error" not in result
        assert HIVE_EPOCH_KEY not in result
        assert HIVE_SHARD_KEY not in result
    stats = fed.stats()
    assert stats["aggregate"]["epochs"] == [2, 1, 1]
    assert stats["aggregate"]["completed"] == len(gate_ids)

    # >= 1 cross-shard steal reconciles in /api/stats (and recovery
    # replay preserved the owner's steal books across the kill)
    assert stats["aggregate"]["steals_total"] >= 1, stats["aggregate"]
    assert any(key.startswith("0->")
               for key in stats["aggregate"]["steals"])

    # the victim shard's in-flight job resumed at step >= 1 on a
    # survivor — its only path: the holder died with the shard, so the
    # resume state crossed the crash through shard 0's WAL
    resumed = fed.completed[victim_job]
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

    # one stitched flight record spanning the steal and both epochs:
    # the victim job's record (whole on its owner) carries grants from
    # epoch 1 AND epoch 2 plus the recovery marker; the steal marker
    # sits on the stolen job's record (the victim itself when the
    # preferred selection found one)
    record = fed.flight(victim_job)
    events = [e["event"] for e in record["events"]]
    grant_epochs = {e.get("epoch") for e in record["events"]
                    if e["event"] == "grant"}
    assert "hive_recovered" in events
    assert {1, 2} <= grant_epochs, record["events"]
    stolen_records = [
        job_id for job_id in gate_ids
        if any(e["event"] == "stolen"
               for e in (fed.flight(job_id) or {}).get("events", []))]
    assert stolen_records, "no stolen flight record anywhere"
    assert fed.verify_flights(gate_ids) == []

    # riding-through survivors replayed their spools LIVE (every
    # envelope that spooled during the outage drained on heal)
    live_total = sum(
        worker.metrics.get("chiaswarm_dead_letter_replayed_total")
        .value(when="live")
        for worker in workers
        if worker.settings.worker_name != victim)
    assert live_total >= spooled_total, (live_total, spooled_total)


# ---------------------------------------------------------------------------
# nightly seeded shard-kill soak (CI satellite; replay with
#   CHIASWARM_SOAK_SEED=<run id> pytest tests/test_federation.py --slow
#   -k soak)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_federated_shard_restart_soak_exactly_once(tmp_path):
    """Nightly federation soak (seed = run id): a seeded chaos job mix
    over 3 journaled shards with seeded mid-run shard-SIGKILL/restart
    cycles under 3 riding-through multiplexed workers. Every issued
    job settles exactly once FLEET-WIDE, and every flight record is
    complete on its owner shard."""
    import os
    import random

    seed = os.environ.get("CHIASWARM_SOAK_SEED", "fed-soak-default")
    n_jobs = int(os.environ.get("CHIASWARM_SOAK_JOBS", "36"))
    rng = random.Random(f"fed-soak:{seed}")
    scripts = ([["ok"]] * 5 + [["slow"]] * 3 + [["oom", "ok"]] * 2
               + [["fetch", "ok"]] * 2 + [["crash"]] + [["fatal"]])
    jobs = [_job(f"fsoak-{i}", chaos=list(rng.choice(scripts)))
            for i in range(n_jobs)]
    restarts = sorted(rng.sample(range(n_jobs // 5, 4 * n_jobs // 5), 2))
    kill_order = [rng.randrange(3) for _ in restarts]

    async def scenario():
        fed = FederatedHive(n_shards=3, journal_root=tmp_path / "hive",
                            journal_fsync=False, lease_s=2.0,
                            delay_s=0.0, max_attempts=6,
                            max_jobs_per_poll=3)
        await fed.start()
        for job in jobs:
            fed.submit(job)
        workers = [_worker(
            fed_settings(fed.worker_uri(), f"fsoak-{tag}",
                         job_deadline_s=0.5),
            executor=ChaoticExecutor(hang_s=1.0, slow_s=0.1))
            for tag in ("a", "b", "c")]
        tasks = {w.settings.worker_name: asyncio.create_task(w.run())
                 for w in workers}
        cycles = 0
        try:
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                settled = (len(fed.completed) + len(fed.abandoned))
                if cycles < len(restarts) and \
                        settled >= restarts[cycles]:
                    # the seeded kill/restart cycle: SIGKILL one
                    # shard, then recover it from ITS journal on the
                    # same port while the other two keep serving
                    index = kill_order[cycles]
                    await fed.kill_shard(index)
                    await asyncio.sleep(0.3)  # let outages flip
                    await fed.restart_shard(index)
                    cycles += 1
                    # re-check thresholds before the settled-break: a
                    # burst can settle EVERYTHING during the restart
                    # awaits, and the remaining cycles must still run
                    # (killing a drained shard still proves recovery)
                    continue
                if len(fed.completed) + len(fed.abandoned) >= n_jobs:
                    break
                fed.sweep()
                await asyncio.sleep(0.05)
        finally:
            for worker in workers:
                worker.request_stop()
            await asyncio.gather(*(asyncio.wait_for(t, timeout=30)
                                   for t in tasks.values()),
                                 return_exceptions=True)
            await fed.stop()
        return fed, cycles

    fed, cycles = asyncio.run(scenario())
    assert cycles == 2
    issued = [j["id"] for j in jobs]
    completed = set(fed.completed)
    abandoned = set(fed.abandoned)
    assert completed.isdisjoint(abandoned)
    assert completed | abandoned == set(issued), \
        sorted(set(issued) - completed - abandoned)
    uploaded = fed.uploaded_ids()
    assert len(uploaded) == len(set(uploaded))
    # each killed shard recovered through its OWN journal
    epochs = fed.stats()["aggregate"]["epochs"]
    assert sum(epochs) == 3 + len(restarts), epochs
    # flight completeness FLEET-WIDE (the chaos-soak.yml gate)
    assert fed.verify_flights(issued, require_settled=False) == []
    assert fed.verify_flights(sorted(completed)) == []
