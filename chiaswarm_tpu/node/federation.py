"""swarmfed (ISSUE 17): the federated hive — a sharded control plane.

The reference architecture is ONE hive at chiaswarm.ai: a single
process, single WAL, single port. PR 14 made that hive crash-safe but
left it singular — the scaling AND blast-radius bottleneck between "a
durable hive" and the ROADMAP north-star. This module spends every
prerequisite PR 13/14 landed to make the control plane survive the
loss of any one of its own parts:

- **ShardRouter**: the job space partitions across H shards by a
  *stable* hash of the job id (hashlib, never Python's per-process
  salted ``hash()``) — the same job id maps to the same shard before
  and after any number of shard restarts, which is what keeps
  exactly-once settlement hash-routable across crashes.
- **ShardHive**: a full :class:`~chiaswarm_tpu.node.minihive.MiniHive`
  per shard — its own port, its own :class:`HiveJournal` under
  ``<root>/hive/<shard>/``, its own epoch book — so PR-14 recovery
  stays deterministic *per shard*. Federated grants carry
  :data:`HIVE_SHARD_KEY` so the worker routes each upload to the
  owner; a result landing on the WRONG shard forwards through the
  router to the owner, whose settle set stays the single source of
  truth (a duplicate is acked ``duplicate`` there, never
  double-settled anywhere).
- **Cross-shard work stealing**: a poll that finds its shard empty
  pulls one job from the deepest-backlog peer through the router. The
  grant is journaled by the OWNING shard (lease, attempt count, epoch
  stamp, flight record — all the owner's), so exactly-once settlement
  and recovery replay are exactly the PR-14 machinery; the steal adds
  only a journaled ``stolen`` marker and a ``{from,to}``-labeled
  counter that replay rebuilds identically.
- **FederatedHive**: the front — submits/settles by hash, serves the
  aggregated ``/api/fleet``, ``/api/stats`` (fleet-wide
  reconciliation) and ``/api/flight/<id>`` (trace ids are already
  globally unique, so PR-13 stitching generalizes: a stolen job's
  record lives whole on its owner), and owns shard lifecycle incl.
  :meth:`kill_shard` / :meth:`restart_shard` (the PR-14 SIGKILL
  contract, per shard).

Wire parity: with H=1 (or through a plain un-federated MiniHive) no
``hive_shard`` key is ever stamped — the reference hive contract is
byte-identical to PR 14's.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import time
from pathlib import Path
from typing import Any, Callable, Iterable

from chiaswarm_tpu.node.hivelog import HIVE_SHARD_KEY, HiveJournal
from chiaswarm_tpu.node.minihive import MiniHive, kill_hive, restart_hive
from chiaswarm_tpu.obs.metrics import Registry, render_all

log = logging.getLogger("chiaswarm.federation")

__all__ = ["HIVE_SHARD_KEY", "FederatedHive", "ShardHive", "ShardRouter",
           "shard_of"]


def shard_of(job_id: Any, n_shards: int) -> int:
    """Stable job-id -> shard index. hashlib, NOT ``hash()``: Python
    salts ``hash()`` per process, which would re-partition the job
    space on every restart and break hash-routed exactly-once
    settlement (the same job id must find the same shard before and
    after a recovery)."""
    if n_shards <= 1:
        return 0
    digest = hashlib.sha256(str(job_id).encode("utf-8")).digest()
    return int.from_bytes(digest[:8], "big") % n_shards


class ShardRouter:
    """The consistent-hash partition of the job space across H shards.
    Pure function of (job id, H) — no state, so every participant
    (front, shards, workers, tests) computes the same owner."""

    def __init__(self, n_shards: int) -> None:
        self.n_shards = max(1, int(n_shards))

    def owner_index(self, job_id: Any) -> int:
        return shard_of(job_id, self.n_shards)


class ShardHive(MiniHive):
    """One shard of a federated hive: a full MiniHive (own journal, own
    epoch book, own port) plus the three federation seams — shard-key
    stamping on grants, cross-shard stealing on empty polls, and
    wrong-shard upload forwarding to the owner."""

    def __init__(self, *args: Any, shard_index: int = 0,
                 **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self.shard_index = int(shard_index)
        #: back-reference set by FederatedHive.attach(); None means
        #: un-federated — every seam below degrades to plain MiniHive
        self.federation: "FederatedHive | None" = None
        m = self.metrics
        # steal accounting lives on the OWNER's registry (the grant is
        # the owner's journaled state transition, so replay rebuilds
        # this counter identically — /api/stats reconciles across
        # restarts). Pre-seeded with the self-pair so the family
        # renders zeroes from scrape one.
        self._steals = m.counter(
            "chiaswarm_hive_steals_total",
            "cross-shard steal grants journaled by this (owning) shard",
            ("from", "to"))
        self._steals.inc(0, **{"from": str(self.shard_index),
                               "to": str(self.shard_index)})
        self._forwarded = m.counter(
            "chiaswarm_hive_shard_forwarded_uploads_total",
            "uploads that landed on this (wrong) shard and were "
            "forwarded through the router to the owner")
        self._forwarded.inc(0)

    # ---- federation seams -----------------------------------------------

    def _federated(self) -> bool:
        fed = self.federation
        return fed is not None and fed.router.n_shards > 1

    def _take_jobs(self, worker_name: str) -> list[dict[str, Any]]:
        out = super()._take_jobs(worker_name)
        if not self._federated():
            return out
        if out:
            for payload in out:
                payload[HIVE_SHARD_KEY] = self.shard_index
            return out
        # empty poll on this shard: hot-spot drain — pull ONE job from
        # the deepest-backlog peer through the router. The grant below
        # is journaled by the OWNER (lease, attempt, epoch, flight),
        # so exactly-once settlement and recovery replay are unmoved.
        return self.federation.steal_for(self, worker_name)

    def steal_to(self, worker_name: str, to_shard: int
                 ) -> list[dict[str, Any]]:
        """Owner side of a steal: grant at most one queued job to a
        worker whose poll landed on (empty) shard ``to_shard``. The
        grant runs the normal journaled handout path on THIS shard;
        the steal itself is an extra journaled marker + the
        ``{from,to}`` counter, both rebuilt identically by replay."""
        saved = self.max_jobs_per_poll
        self.max_jobs_per_poll = 1
        try:
            # explicit super-call past ShardHive: the steal must never
            # re-enter the empty-poll steal seam on the owner
            granted = super()._take_jobs(worker_name)
        finally:
            self.max_jobs_per_poll = saved
        now = self._clock()
        for payload in granted:
            payload[HIVE_SHARD_KEY] = self.shard_index
            job_id = str(payload.get("id"))
            self._steals.inc(**{"from": str(self.shard_index),
                                "to": str(to_shard)})
            self.flights.note(job_id, "stolen", t=now,
                              from_shard=self.shard_index,
                              to_shard=int(to_shard), worker=worker_name)
            self._journal("stolen", id=job_id, t=now,
                          from_shard=self.shard_index,
                          to_shard=int(to_shard), worker=worker_name)
            log.info("job %s stolen from shard %d by %s (polled shard "
                     "%d)", job_id, self.shard_index, worker_name,
                     to_shard)
        self._journal_commit()
        return granted

    def _record_result(self, result: dict[str, Any],
                       worker_name: str) -> dict[str, Any]:
        # the shard identity echo is routing metadata, never stored
        # state — popped like the epoch stamp and the span digest
        result.pop(HIVE_SHARD_KEY, None)
        if self._federated():
            owner = self.federation.owner_shard(result.get("id"))
            if owner is not None and owner is not self:
                # an upload for a job this shard does not own (a stolen
                # job's worker mis-routed, a retrying client with a
                # stale shard map): forward through the router — the
                # OWNER's settle set decides exactly-once, so a
                # duplicate is acked `duplicate` there and never
                # double-settles anywhere
                self._forwarded.inc()
                log.warning("upload for %s landed on shard %d (owner "
                            "is shard %d); forwarding",
                            result.get("id"), self.shard_index,
                            owner.shard_index)
                return owner._record_result(result, worker_name)
        return super()._record_result(result, worker_name)

    def _apply_journal_event(self, record: dict[str, Any],
                             jobs: dict[str, dict[str, Any]]) -> None:
        if str(record.get("ev") or "") == "stolen":
            # replay rebuilds the steal books exactly: counter + flight
            # marker (the grant itself replays as a normal grant event)
            job_id = (None if record.get("id") is None
                      else str(record.get("id")))
            self._steals.inc(
                **{"from": str(record.get("from_shard") or 0),
                   "to": str(record.get("to_shard") or 0)})
            self.flights.note(job_id, "stolen",
                              t=float(record.get("t") or 0.0),
                              from_shard=record.get("from_shard"),
                              to_shard=record.get("to_shard"),
                              worker=record.get("worker"))
            return
        super()._apply_journal_event(record, jobs)

    def stats(self) -> dict[str, Any]:
        data = super().stats()
        data["shard_index"] = self.shard_index
        data["steals"] = {
            f"{key[0]}->{key[1]}": value
            for key, value in self._steals.series().items()
            if value > 0 or key[0] != key[1]
        }
        return data


class FederatedHive:
    """The federation front: H ShardHives + the router + the
    aggregation plane. Submits and settles route by the stable hash;
    each shard keeps its own journal/epoch book so per-shard recovery
    is exactly PR 14's contract. The front's own HTTP surface serves
    the FLEET-wide views; workers talk to the shards directly (the
    shard uris are the worker-facing control plane)."""

    def __init__(self, n_shards: int = 3, *,
                 journal_root: Path | str | None = None,
                 hive_cls: type | None = None,
                 journal_fsync: bool = True,
                 steal: bool = True,
                 clock: Callable[[], float] = time.monotonic,
                 **shard_kwargs: Any) -> None:
        self.router = ShardRouter(n_shards)
        self.hive_cls = hive_cls or ShardHive
        self.steal_enabled = bool(steal)
        self._clock = clock
        self.shard_kwargs = dict(shard_kwargs)
        self.journal_root = (None if journal_root is None
                             else Path(journal_root))
        self.journals: list[HiveJournal | None] = []
        self.shards: list[ShardHive] = []
        self.ports: list[int] = [0] * self.router.n_shards
        # shards killed and not yet recovered: the dead OBJECT stays in
        # ``shards`` for the harness to read, but its memory is garbage
        # and its journal detached, so no peer may route through it
        self.down: set[int] = set()
        for index in range(self.router.n_shards):
            journal = None
            if self.journal_root is not None:
                # the documented shard layout: <root>/hive/<shard>/
                journal = HiveJournal(self.journal_root / str(index),
                                      fsync=journal_fsync)
            self.journals.append(journal)
            shard = self.hive_cls(shard_index=index, journal=journal,
                                  clock=clock, **self.shard_kwargs)
            self.attach(shard, index)
            self.shards.append(shard)
        # ---- the front's own observability plane ----
        self.metrics = Registry()
        self._depth_gauge = self.metrics.gauge(
            "chiaswarm_hive_shard_depth",
            "pending (queued, unleased) jobs per hive shard", ("shard",))
        self._epoch_gauge = self.metrics.gauge(
            "chiaswarm_hive_shard_epoch",
            "current epoch per hive shard (0 = journaling off)",
            ("shard",))
        self._leased_gauge = self.metrics.gauge(
            "chiaswarm_hive_shard_leased",
            "leased (in-flight) jobs per hive shard", ("shard",))
        for index in range(self.router.n_shards):
            self._depth_gauge.set(0, shard=str(index))
            self._leased_gauge.set(0, shard=str(index))
            self._epoch_gauge.set(0, shard=str(index))
        self.metrics.add_collector(self._refresh_shard_gauges)
        self._refresh_shard_gauges()
        self._app = None
        self._runner = None
        self.uri = ""
        self.port = 0
        # swarmplan (ISSUE 19): a FleetPlanner attached to the FRONT
        # plans fleet-wide over the merged fleet_snapshot; None keeps
        # the pre-planner surface (404 /api/plan, hint-free acks)
        self.planner: Any = None

    # ---- wiring ---------------------------------------------------------

    def attach(self, shard: ShardHive, index: int) -> ShardHive:
        """Wire a shard (fresh or recovered) into the federation at
        ``index``: the back-reference gives it the router + peers —
        and the fleet planner, so a recovered shard's heartbeat acks
        resume carrying placement hints without re-attachment."""
        shard.shard_index = int(index)
        shard.federation = self
        shard.planner = getattr(self, "planner", None)
        if index < len(self.shards):
            self.shards[index] = shard
        self.down.discard(int(index))
        return shard

    @property
    def n_shards(self) -> int:
        return self.router.n_shards

    def owner_index(self, job_id: Any) -> int:
        return self.router.owner_index(job_id)

    def owner_shard(self, job_id: Any) -> ShardHive | None:
        index = self.router.owner_index(job_id)
        if 0 <= index < len(self.shards):
            return self.shards[index]
        return None

    def shard_uris(self) -> list[str]:
        return [shard.uri for shard in self.shards]

    def worker_uri(self) -> str:
        """The worker-facing control plane: every shard uri, in index
        order (Settings.hive_uris parses this back per shard)."""
        return ",".join(self.shard_uris())

    # ---- lifecycle ------------------------------------------------------

    async def start(self, *, front_port: int = 0) -> str:
        for index, shard in enumerate(self.shards):
            await shard.start(port=self.ports[index] or 0)
            self.ports[index] = shard.port
        from aiohttp import web

        self._app = web.Application()
        self._app.router.add_get("/api/stats", self._stats_endpoint)
        self._app.router.add_get("/api/fleet", self._fleet_endpoint)
        self._app.router.add_get("/api/plan", self._plan_endpoint)
        self._app.router.add_get("/api/shards", self._shards_endpoint)
        self._app.router.add_get("/api/flight", self._flights_endpoint)
        self._app.router.add_get("/api/flight/{job_id}",
                                 self._flight_endpoint)
        self._app.router.add_get("/metrics", self._metrics_endpoint)
        self._runner = web.AppRunner(self._app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", front_port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        self.uri = f"http://127.0.0.1:{self.port}"
        log.info("federated hive up: front %s, shards %s", self.uri,
                 self.shard_uris())
        return self.uri

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
        for shard in self.shards:
            try:
                await shard.stop()
            except Exception:  # a dead shard must not block the rest
                log.exception("shard %d stop failed", shard.shard_index)

    async def kill_shard(self, index: int) -> int:
        """SIGKILL one shard in-process (the PR-14 contract, scoped):
        its in-memory state is garbage, its journal the only survivor;
        every OTHER shard keeps serving — the blast radius this module
        exists to bound. Returns the port for :meth:`restart_shard`."""
        shard = self.shards[index]
        self.down.add(int(index))
        port = await kill_hive(shard)
        self.ports[index] = port
        log.warning("shard %d killed on port %d (%d shard(s) still "
                    "serving)", index, port, self.n_shards - 1)
        return port

    async def restart_shard(self, index: int, *,
                            lease_grace_s: float = 0.0) -> ShardHive:
        """Recover shard ``index`` from ITS OWN journal on its old port
        (riding-through worker sessions heal on their next poll) and
        wire it back into the federation. Deterministic per shard —
        no other shard's state participates."""
        journal = self.journals[index]
        if journal is None:
            raise RuntimeError(
                f"shard {index} has no journal to recover from")
        recovered = await restart_hive(
            journal, port=self.ports[index], hive_cls=self.hive_cls,
            lease_grace_s=lease_grace_s, shard_index=index,
            clock=self._clock, **self.shard_kwargs)
        self.attach(recovered, index)
        return recovered

    # ---- hash-routed control plane --------------------------------------

    def submit(self, job: dict[str, Any]) -> int:
        """Route a submission to its owner shard; returns the index."""
        index = self.router.owner_index(job.get("id"))
        self.shards[index].submit(job)
        return index

    def submit_job(self, job: dict[str, Any]) -> int:
        """LoadHive-compatible alias (the swarmload harness seam)."""
        index = self.router.owner_index(job.get("id"))
        shard = self.shards[index]
        submit = getattr(shard, "submit_job", None)
        if callable(submit):
            submit(job)
        else:
            shard.submit(job)
        return index

    def sweep(self) -> list[str]:
        out: list[str] = []
        for shard in self.shards:
            out.extend(shard.sweep())
        return out

    def steal_for(self, thief: ShardHive, worker_name: str
                  ) -> list[dict[str, Any]]:
        """Router side of a steal: find the deepest-backlog peer of
        ``thief`` and let the OWNER grant one job to the polling
        worker. No backlog anywhere -> nothing handed (the poll stays
        an honest empty poll)."""
        if not self.steal_enabled:
            return []
        # a shard partitioned from this worker must not hand it work
        # through the back door — the lease would live on a hive the
        # worker cannot heartbeat or upload to. A killed shard hands
        # out nothing at all: a grant from its dead memory is in no
        # journal, so the recovered shard would meet the job's upload
        # as a digest of an attempt it never granted
        peers = [shard for shard in self.shards
                 if shard is not thief and shard.pending_jobs
                 and shard.shard_index not in self.down
                 and worker_name not in shard.partitioned]
        if not peers:
            return []
        victim = max(peers, key=lambda shard: len(shard.pending_jobs))
        return victim.steal_to(worker_name, thief.shard_index)

    # ---- chaos fan-out (harness parity with MiniHive) -------------------

    def partition(self, worker_name: str) -> None:
        for shard in self.shards:
            shard.partition(worker_name)

    def heal(self, worker_name: str) -> None:
        for shard in self.shards:
            shard.heal(worker_name)

    def expire_worker(self, worker_name: str) -> list[str]:
        out: list[str] = []
        for shard in self.shards:
            out.extend(shard.expire_worker(worker_name))
        return out

    def leased_ids(self, worker_name: str) -> list[str]:
        out: list[str] = []
        for shard in self.shards:
            out.extend(shard.leased_ids(worker_name))
        return sorted(out)

    def lease_holder(self, job_id: Any) -> str | None:
        shard = self.owner_shard(job_id)
        return None if shard is None else shard.lease_holder(job_id)

    # ---- merged read views (the reconciliation surface) -----------------

    def _merged_dict(self, attr: str) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for shard in self.shards:
            out.update(getattr(shard, attr))
        return out

    def _merged_list(self, attr: str) -> list[Any]:
        out: list[Any] = []
        for shard in self.shards:
            out.extend(getattr(shard, attr))
        return out

    @property
    def completed(self) -> dict[str, dict[str, Any]]:
        return self._merged_dict("completed")

    @property
    def checkpoints(self) -> dict[str, dict[str, Any]]:
        return self._merged_dict("checkpoints")

    @property
    def submitted_at(self) -> dict[str, float]:
        return self._merged_dict("submitted_at")

    @property
    def abandoned(self) -> list[str]:
        return self._merged_list("abandoned")

    @property
    def results(self) -> list[dict[str, Any]]:
        return self._merged_list("results")

    @property
    def duplicate_results(self) -> list[dict[str, Any]]:
        return self._merged_list("duplicate_results")

    @property
    def issued_ids(self) -> list[str]:
        return self._merged_list("issued_ids")

    @property
    def pending_jobs(self) -> list[dict[str, Any]]:
        return self._merged_list("pending_jobs")

    def uploaded_ids(self) -> list[str]:
        out: list[str] = []
        for shard in self.shards:
            out.extend(shard.uploaded_ids())
        return out

    async def wait_for_results(self, n: int, timeout: float = 30.0
                               ) -> list[dict[str, Any]]:
        deadline = time.monotonic() + float(timeout)
        while time.monotonic() < deadline:
            results = self.results
            if len(results) >= n:
                return results
            await asyncio.sleep(0.05)
        raise asyncio.TimeoutError(
            f"federation: {len(self.results)}/{n} results after "
            f"{timeout}s")

    def verify_flights(self, job_ids: Iterable[Any],
                       **kwargs: Any) -> list[dict]:
        """Fleet-wide flight completeness: each job audits against its
        OWNER shard's recorder (a stolen job's record lives whole on
        the owner — the grant, the steal marker, both epochs, and the
        settle are one stitched story there). ``kwargs`` pass through
        to :meth:`FlightRecorder.verify` (e.g. ``require_settled``)."""
        by_owner: dict[int, list[Any]] = {}
        for job_id in job_ids:
            by_owner.setdefault(self.owner_index(job_id),
                                []).append(job_id)
        problems: list[dict] = []
        for index, ids in sorted(by_owner.items()):
            problems.extend(
                self.shards[index].flights.verify(ids, **kwargs))
        return problems

    def flight(self, job_id: Any) -> dict[str, Any] | None:
        shard = self.owner_shard(job_id)
        return None if shard is None else shard.flights.get(job_id)

    # ---- aggregation plane ----------------------------------------------

    def _refresh_shard_gauges(self) -> None:
        for shard in self.shards:
            label = str(shard.shard_index)
            self._depth_gauge.set(len(shard.pending_jobs), shard=label)
            self._leased_gauge.set(len(shard.leases), shard=label)
            self._epoch_gauge.set(shard.hive_epoch, shard=label)

    def steals_total(self) -> int:
        return int(sum(
            value for shard in self.shards
            for key, value in shard._steals.series().items()))

    def stats(self) -> dict[str, Any]:
        """The fleet-wide ``/api/stats`` reconciliation: per-shard
        books plus the cross-shard totals the exactly-once tests (and
        an operator mid-incident) reconcile against — one settle per
        issued job across ALL shards, steals counted once (by their
        owner), forwards visible."""
        shards = [shard.stats() for shard in self.shards]
        self._refresh_shard_gauges()
        steals: dict[str, float] = {}
        for shard in self.shards:
            for key, value in shard._steals.series().items():
                if value <= 0 and key[0] == key[1]:
                    continue
                steals[f"{key[0]}->{key[1]}"] = \
                    steals.get(f"{key[0]}->{key[1]}", 0) + value
        return {
            "n_shards": self.n_shards,
            "shards": shards,
            "aggregate": {
                "pending": sum(s["pending"] for s in shards),
                "leased": sum(len(s["leased"]) for s in shards),
                "completed": sum(s["completed"] for s in shards),
                "duplicates": sum(s["duplicates"] for s in shards),
                "abandoned": sorted(
                    job_id for s in shards for job_id in s["abandoned"]),
                "epochs": [s["hive_epoch"] for s in shards],
                "steals": steals,
                "steals_total": self.steals_total(),
                "forwarded_uploads": int(sum(
                    shard._forwarded.value()
                    for shard in self.shards)),
            },
        }

    def fleet_snapshot(self) -> dict[str, Any]:
        """The aggregated ``/api/fleet``: per-worker entries merged
        freshest-wins across shards (a multiplexed worker heartbeats
        every shard), numeric aggregates summed where they are
        per-shard truth (queue state) and taken from the merged worker
        map where they are per-worker truth (chips, occupancy) — a
        worker reporting to H shards must count once, not H times."""
        now = self._clock()
        per_shard = [shard.fleet_snapshot() for shard in self.shards]
        workers: dict[str, dict[str, Any]] = {}
        for snapshot in per_shard:
            for name, entry in snapshot["workers"].items():
                held = workers.get(name)
                if held is None or entry["age_s"] < held["age_s"]:
                    # freshest snapshot wins; lease counts are
                    # per-shard, so they sum below instead
                    merged = dict(entry)
                    merged["leased_jobs"] = 0
                    workers[name] = merged
        for name in workers:
            workers[name]["leased_jobs"] = sum(
                len(shard.leased_ids(name)) for shard in self.shards)
        active = {name: w for name, w in workers.items()
                  if w.get("live") and not w.get("partitioned")}

        def total(key: str) -> float:
            return round(sum(float(w.get(key) or 0.0)
                             for w in active.values()), 4)

        return {
            "at_s": round(now, 6),
            "n_shards": self.n_shards,
            "workers": workers,
            "aggregate": {
                "workers_reporting": len(workers),
                "workers_live": len({
                    name for shard in self.shards
                    for name in shard.live_workers()}),
                "chips_in_service": int(total("chips_in_service")),
                "arrival_rate_rows_s": total("arrival_rate_rows_s"),
                "queue_depth": int(total("queue_depth")),
                "inflight_jobs": int(total("inflight_jobs")),
                "jobs_done": int(total("jobs_done")),
                "observed_arrival_jobs_s": round(sum(
                    s["aggregate"]["observed_arrival_jobs_s"]
                    for s in per_shard), 4),
                # per-model demand summed across shards (swarmplan,
                # ISSUE 19): jobs hash-route by id, so every shard
                # sees a slice of each model's stream — the fleet-wide
                # rate the placement plan needs is the sum
                "model_arrival_jobs_s": self._merged_model_rates(
                    per_shard),
                "pending_jobs": sum(
                    s["aggregate"]["pending_jobs"] for s in per_shard),
                "leased_jobs": sum(
                    s["aggregate"]["leased_jobs"] for s in per_shard),
                "completed_jobs": sum(
                    s["aggregate"]["completed_jobs"] for s in per_shard),
                "abandoned_jobs": sum(
                    s["aggregate"]["abandoned_jobs"] for s in per_shard),
            },
        }

    @staticmethod
    def _merged_model_rates(per_shard: list[dict[str, Any]]
                            ) -> dict[str, float]:
        merged: dict[str, float] = {}
        for snapshot in per_shard:
            rates = snapshot["aggregate"].get("model_arrival_jobs_s") or {}
            for model, rate in rates.items():
                merged[model] = merged.get(model, 0.0) + float(rate)
        return {model: round(rate, 4)
                for model, rate in sorted(merged.items())}

    # ---- the fleet planner's journal seam (swarmplan, ISSUE 19) ---------
    #
    # The front owns no journal; shard 0's book records fleet-wide
    # intent (the same convention the merged read views follow — one
    # deterministic home, replayed by that shard's recovery).

    def record_plan(self, decision: dict[str, Any]) -> None:
        self.shards[0].record_plan(decision)

    @property
    def last_plan(self) -> dict[str, Any] | None:
        return self.shards[0].last_plan

    # ---- front endpoints ------------------------------------------------

    async def _stats_endpoint(self, request):
        from aiohttp import web

        return web.json_response(self.stats())

    async def _fleet_endpoint(self, request):
        from aiohttp import web

        return web.json_response(self.fleet_snapshot())

    async def _plan_endpoint(self, request):
        """Fleet-wide ``GET /api/plan`` (swarmplan, ISSUE 19): the
        supervisor contract served from the front — one poll address
        for the whole federation."""
        from aiohttp import web

        if self.planner is None:
            return web.json_response({"error": "no planner attached"},
                                     status=404)
        return web.json_response(self.planner.plan_snapshot())

    async def _shards_endpoint(self, request):
        """``GET /api/shards`` (ISSUE 19 satellite, PR-17 residue): the
        front is an aggregation plane, not a proxy — workers must dial
        the shards directly. This endpoint closes the bootstrap gap: a
        worker configured with ONE front address fetches the shard uri
        list here (``bootstrap_shard_uris``) instead of carrying a
        hand-configured ``hive_shard_uris`` tuple."""
        from aiohttp import web

        return web.json_response({
            "n_shards": self.n_shards,
            "shards": self.shard_uris(),
            "worker_uri": self.worker_uri(),
        })

    async def _flights_endpoint(self, request):
        from aiohttp import web

        jobs: list[str] = []
        for shard in self.shards:
            jobs.extend(shard.flights.job_ids())
        return web.json_response({"n_shards": self.n_shards,
                                  "jobs": sorted(jobs)})

    async def _flight_endpoint(self, request):
        from aiohttp import web

        job_id = request.match_info.get("job_id", "")
        record = self.flight(job_id)
        if record is None:
            return web.json_response(
                {"status": "unknown",
                 "error": f"no flight record for job {job_id!r} on "
                          f"shard {self.owner_index(job_id)}"},
                status=404)
        return web.json_response(dict(
            record, shard=self.owner_index(job_id)))

    async def _metrics_endpoint(self, request):
        from aiohttp import web

        from chiaswarm_tpu.obs.metrics import CONTENT_TYPE

        body = render_all([self.metrics]
                          + [shard.metrics for shard in self.shards])
        return web.Response(text=body, content_type="text/plain",
                            charset="utf-8",
                            headers={"X-Content-Type": CONTENT_TYPE})


async def bootstrap_shard_uris(front_uri: str, *,
                               timeout_s: float = 10.0
                               ) -> tuple[str, ...]:
    """Resolve a federated front address into the worker-facing shard
    uri list via ``GET /api/shards`` (ISSUE 19 satellite). The worker
    consumes this at startup when ``hive_front_uri`` is set — one
    operator-configured address instead of a hand-maintained shard
    list that silently goes stale when the federation is resized.
    Raises on an unreachable front or a body with no shards: serving
    against a guessed control plane is worse than failing loudly."""
    import aiohttp

    url = front_uri.rstrip("/") + "/api/shards"
    timeout = aiohttp.ClientTimeout(total=max(0.1, float(timeout_s)))
    async with aiohttp.ClientSession(timeout=timeout) as session:
        async with session.get(url) as response:
            response.raise_for_status()
            body = await response.json()
    uris = tuple(str(u) for u in (body.get("shards") or ()) if u)
    if not uris:
        raise RuntimeError(
            f"front {front_uri} returned no shard uris: {body!r}")
    return uris
