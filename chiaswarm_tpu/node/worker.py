"""The worker daemon: poll the hive, execute on mesh slots, upload results.

Capability parity with swarm/worker.py:21-195, with the reference's
concurrency bug fixed: the reference acquires the GPU semaphore both while
*polling* and while *executing* (worker.py:60,108 + 118,127), serializing
the two on single-GPU nodes (SURVEY.md §3.1). Here backpressure is the
bounded ``work_queue`` alone — the poll loop simply waits for queue space,
and each slot task owns its own execution; no shared semaphore.

Fault containment (node/resilience.py) — the reference's only failure
story is the hive's timeout detector (swarm/worker.py:92-97); here
failures are contained at the JOB level and reported explicitly:

- every burst runs under a per-workflow **deadline** (settings.py:
  ``deadline_for``); a timed-out or crashed job uploads a structured
  error envelope through the normal result path, so the hive learns of
  failures in seconds;
- the **degradation ladder**: transient faults (input-image fetch blips,
  device OOM on a coalesced burst) re-run locally with capped backoff +
  jitter — OOM'd bursts split and re-run serially — and a per-model
  circuit breaker quarantines a model in the registry after K consecutive
  permanent failures;
- **graceful shutdown**: SIGTERM/SIGINT stop polling first, in-flight
  slots and the result queue drain (bounded by the drain timeouts), and
  results that exhaust upload retries spool to a disk dead-letter
  directory that replays on the next startup — paid chip time is never
  silently discarded.

Startup gates mirror the reference's (worker.py:166-181): an accelerator
must be present (TPU/virtual-CPU mesh instead of CUDA), logging configured,
and matmul precision pinned (bf16 — the TPU analog of TF32 knobs).
"""

from __future__ import annotations

import asyncio
import functools
import logging
import random
import re
import signal
import time
from pathlib import Path
from typing import Any

import aiohttp
import jax

from chiaswarm_tpu.obs import flight as obs_flight
from chiaswarm_tpu.obs import metrics as obs_metrics
from chiaswarm_tpu.obs import profiling as obs_profiling
from chiaswarm_tpu.obs import trace as obs_trace

from chiaswarm_tpu.core.chip_pool import ChipPool
from chiaswarm_tpu.core.compile_cache import single_chip_rows
from chiaswarm_tpu.node.executor import (
    do_work,
    do_work_batch,
    error_result,
    job_rows,
    rows_cap,
)
from chiaswarm_tpu.node.hive import BadWorkerError, HiveClient
from chiaswarm_tpu.node.hivelog import HIVE_EPOCH_KEY, HIVE_SHARD_KEY
from chiaswarm_tpu.node.logging_setup import setup_logging
from chiaswarm_tpu.node.overload import OverloadController
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.resilience import (
    BREAKER_KINDS,
    RETRYABLE_KINDS,
    Backoff,
    BreakerBoard,
    CheckpointSpool,
    DeadLetterSpool,
    HiveSession,
    ResilienceStats,
    backoff_delay,
    classify_exception,
    classify_result,
    hive_reachable_error,
)
from chiaswarm_tpu.node.settings import Settings, load_settings
from chiaswarm_tpu.serving.guard import (
    GUARD_RESTART_EXIT_CODE,
    DeviceGuard,
    _slot_devices,
    suggest_hang_budget,
)

log = logging.getLogger("chiaswarm.worker")


class _HiveShard:
    """One hive shard from the worker's side (swarmfed, ISSUE 17): its
    own client, its own outage session (ride-through flips PER SHARD —
    a dead shard degrades only its own traffic while polls continue
    against the rest), its own dead-letter spool namespace, its own
    poll backoff, and its own epoch handshake (each shard recovers from
    its own journal, so epochs are per-shard truth). A single-hive
    worker holds exactly one of these — shard 0 — and the Worker's
    ``hive``/``hive_session``/``dead_letters`` properties alias it, so
    the pre-federation surface is unchanged."""

    def __init__(self, *, index: int, uri: str, client: Any,
                 session: HiveSession, spool: DeadLetterSpool,
                 backoff: Backoff) -> None:
        self.index = int(index)
        self.uri = str(uri)
        self.client = client
        self.session = session
        self.spool = spool
        self.backoff = backoff
        # the hive epoch last seen on THIS shard's grants/heartbeat
        # acks (None against a journal-less shard); echoed on uploads
        # routed here so a recovered shard dedupes pre-crash grants
        self.last_epoch: int | None = None
        # fleet-plane cadence throttle, per shard (each shard serves
        # its own /api/fleet slice of this worker's snapshots)
        self.last_metrics = float("-inf")


def _burst_key(job: dict) -> tuple | None:
    """Cheap raw-job coalescability key (None = never coalesce).

    Conservative pre-filter for the slot burst drain: plain txt2img,
    img2img and inpaint jobs with identical static fields are drained
    together (images themselves differ per job by design — per-job init
    stacks + encode seeds keep solo equality) — the executor's precise
    post-formatting grouping (node/executor.py::
    synchronous_do_work_batch) is the authority (it also sees the FETCHED
    image shapes, which this pre-filter cannot); this just keeps
    non-coalescable traffic on the per-job path so its results upload as
    soon as each job finishes."""
    if job.get("workflow") not in (None, "", "txt2img", "img2img",
                                   "inpaint"):
        return None
    if job.get("resume") is not None:
        # a redelivered job with resume state rides a lane (or runs
        # solo); coalescing it with fresh jobs would discard the resume
        return None
    model = str(job.get("model_name", ""))
    if model.startswith("DeepFloyd/") or "pix2pix" in model:
        return None
    params = job.get("parameters") or {}
    if params.get("controlnet") or params.get("upscale"):
        return None
    image = job.get("image")
    steps = job.get("num_inference_steps")
    guidance = job.get("guidance_scale")
    strength = job.get("strength")
    from chiaswarm_tpu.serving.stepper import stepper_enabled

    if stepper_enabled():
        # lanes carry steps, guidance AND the img2img strength (its
        # start index) PER ROW (serving/stepper.py): jobs differing only
        # in those fields drain as one burst and splice into one lane —
        # since ISSUE 7 that covers img2img and inpaint too, not just
        # txt2img (the mode split below still keeps workloads apart,
        # and the executor's post-format grouping stays the authority
        # for whatever falls back off a lane)
        steps = guidance = strength = None
    return (model, job.get("height"), job.get("width"),
            steps, guidance,
            job.get("lora"), job.get("textual_inversion"),
            job.get("cross_attention_scale"),
            # mode split: generation vs img2img vs inpaint (+ inline
            # image grids; URI-fetched sizes are the executor's job)
            bool(job.get("start_image_uri") or image is not None),
            bool(job.get("mask_image_uri")
                 or job.get("mask_image") is not None),
            strength,
            None if image is None else tuple(getattr(image, "shape", ())),
            repr(sorted(params.items())))




class Worker:
    """One node process: N mesh-slot executors + poll/upload tasks.

    Designed as a class (vs the reference's module globals) so tests can run
    multiple hermetic workers against a FakeHive in one process.

    ``executor`` (an object with async ``do_work(job, slot, registry)`` and
    ``do_work_batch(jobs, slot, registry)``) overrides the real executor —
    the seam the chaos harness (node/chaos.py) uses to inject scripted
    faults under a real worker.
    """

    def __init__(self, settings: Settings | None = None,
                 pool: ChipPool | None = None,
                 registry: ModelRegistry | None = None,
                 hive: HiveClient | None = None,
                 executor: Any | None = None) -> None:
        self.settings = settings or load_settings()
        # registry first: its catalog feeds the default mesh policy
        self.registry = registry or ModelRegistry(
            attn_impl="auto" if self.settings.use_flash_attention else "xla"
        )
        self.pool = pool if pool is not None else self._default_pool()
        # swarmfed (ISSUE 17): the control plane may be H hive shards
        # (settings.hive_uris() — an explicit list, or commas in
        # hive_uri); the worker multiplexes one session bundle per
        # shard. An injected ``hive`` client (the chaos/test seam)
        # pins a single bundle around it.
        self._hive_injected = hive is not None
        self.shards: list[_HiveShard] = self._build_hive_shards(hive)
        self._executor = executor
        # queue bound = total in-flight capacity: per slot, the larger of
        # its pipeline depth (transfer/compute overlap) and its data-axis
        # width (cross-job coalescing needs that many jobs queued). The
        # reference sizes its queue to the GPU count (worker.py:186).
        self.work_queue: asyncio.Queue = asyncio.Queue(
            maxsize=sum(
                max(getattr(slot, "depth", 1), slot.data_width)
                for slot in self.pool))
        self.result_queue: asyncio.Queue = asyncio.Queue()
        self._stop = asyncio.Event()
        self._draining = asyncio.Event()
        self.jobs_done = 0
        # slots currently blocked on work_queue.get(): the burst drain
        # leaves this many jobs in the queue so coalescing on one slot
        # never starves an idle neighbor (multi-slot fairness reserve)
        self._hungry_slots = 0
        # ---- observability (chiaswarm_tpu/obs, ISSUE 4) ----
        # per-WORKER registry + trace ring: hermetic test workers must
        # not bleed counters into each other; process-wide metrics
        # (compile cache, lane step timing) live on obs.metrics.REGISTRY
        # and /metrics serves both
        self.metrics = obs_metrics.Registry()
        self.traces = obs_trace.TraceRing()
        self._job_seconds = self.metrics.histogram(
            "chiaswarm_job_seconds",
            "end-to-end job wall time (poll receipt -> upload settled)")
        self._phase_seconds = self.metrics.histogram(
            "chiaswarm_job_phase_seconds",
            "per-phase job wall time from the trace spans",
            labelnames=("phase",))
        self._jobs_total = self.metrics.counter(
            "chiaswarm_jobs_total",
            "jobs settled (uploaded or dead-lettered), by final outcome",
            labelnames=("outcome",))
        self.metrics.add_collector(self._collect_metrics)
        # ---- fault-tolerance state (node/resilience.py) ----
        self.stats = ResilienceStats(self.metrics)
        # ---- overload control (node/overload.py, ISSUE 9) ----
        # always constructed (its chiaswarm_overload_* families must
        # render zeroes from scrape one), only CONSULTED when the
        # settings gate is on — reference-hive parity keeps it off
        self.overload = OverloadController(
            margin=self.settings.overload_margin,
            backpressure_s=(float(self.settings.backpressure_s)
                            or self.settings.job_deadline_s / 2.0),
            brownout_sheds=self.settings.overload_brownout_sheds,
            window_s=self.settings.overload_window_s,
            cooldown_s=self.settings.overload_cooldown_s,
            admission_cap_rows=self.settings.overload_admission_cap,
            metrics_registry=self.metrics)
        # ---- gray-failure guard (serving/guard.py, ISSUE 10) ----
        # per-worker device-health ledger + healing ladder. Always
        # constructed (its chiaswarm_guard_* families must render
        # zeroes from scrape one); rung ACTIONS apply only when the
        # settings gate is on. Lane drivers and the solo watchdog find
        # it through the slot handle, like the checkpoint spool.
        self.guard = DeviceGuard(
            enabled=self.settings.guard_enabled,
            cache_flush_after=self.settings.guard_cache_flush_after,
            quarantine_after=self.settings.guard_quarantine_after,
            restart_after=self.settings.guard_restart_after,
            metrics_registry=self.metrics)
        for slot in self.pool:
            try:
                slot._guard = self.guard
            except (AttributeError, TypeError):  # exotic slot stubs
                pass
            self.guard.seed_devices(_slot_devices(slot))
        # process exit status: 0, or GUARD_RESTART_EXIT_CODE after the
        # restart rung's graceful drain (supervisors restart-on-73)
        self.exit_code = 0
        self._retry_rng = random.Random(
            f"retry:{self.settings.worker_name}")
        # the registry mirror tolerates stub registries without
        # quarantine support (several worker tests pass object())
        # breaker state persists NEXT TO the dead-letter spool and
        # reloads here: a checkpoint quarantined before a restart stays
        # quarantined after it (the residual cooldown rides the file)
        self.breakers = BreakerBoard(
            threshold=self.settings.breaker_threshold,
            cooldown_s=self.settings.breaker_cooldown_s,
            on_open=getattr(self.registry, "quarantine", None),
            on_close=getattr(self.registry, "unquarantine", None),
            on_probe=getattr(self.registry, "unquarantine", None),
            persist_path=self._breaker_state_path())
        # dead-letter files currently riding the result queue: ONE set
        # across every shard's spool — the live replay must never
        # enqueue a spooled envelope twice, whichever shard healed
        self._replayed_paths: set[str] = set()
        self._dl_replayed = obs_metrics.dead_letter_replayed_counter(
            self.metrics)
        for when in obs_metrics.DEAD_LETTER_REPLAY_WHEN:
            self._dl_replayed.inc(0, when=when)
        # per-shard session-state gauge (swarmfed, ISSUE 17): rendered
        # with zeroes from scrape one, one series per configured shard
        shard_gauge = obs_metrics.hive_shard_session_state_gauge(
            self.metrics)
        for shard in self.shards:
            shard_gauge.set(0, shard=str(shard.index))
        # ---- fleet durability (ISSUE 6) ----
        # resume-state spool next to the dead-letter spool (same
        # per-worker namespacing); lanes snapshot into it via the slot
        # handle, heartbeats push its latest entries to a lease-aware
        # hive, and an acked upload garbage-collects the job's file.
        # Only the heartbeat ever delivers a checkpoint anywhere (the
        # spool is wholesale-cleared at startup), so with heartbeats off
        # — the reference-hive default — the spool is never attached and
        # lanes/solo jobs pay no snapshot cost for state nothing reads.
        self.checkpoints = CheckpointSpool(self._checkpoint_dir())
        if float(self.settings.heartbeat_s or 0) > 0:
            for slot in self.pool:
                try:
                    slot._checkpoint_spool = self.checkpoints
                except (AttributeError, TypeError):  # exotic slot stubs
                    pass
        # jobs between poll receipt and settled upload — the id set the
        # heartbeat keeps leased (insertion-ordered for stable payloads)
        self._inflight: dict[Any, float] = {}
        # swarmfed (ISSUE 17): which shard OWNS each in-flight job's
        # lease (stolen grants arrive via one shard's poll but belong
        # to the owner) — heartbeats and uploads route by this
        self._inflight_shard: dict[Any, int] = {}
        # ---- HBM residency (ISSUE 8, serving/residency.py) ----
        # push the operator's settings into the registry's ledger: an
        # explicit budget override, and the prefetch toggle (idle polls
        # trigger demand-driven warm loads below)
        residency = getattr(self.registry, "residency", None)
        if residency is not None:
            if int(self.settings.residency_budget_bytes or 0) > 0:
                residency.set_budget(
                    int(self.settings.residency_budget_bytes))
            residency.prefetch_enabled = bool(
                self.settings.residency_prefetch
                and residency.prefetch_enabled)

    def _spool_dirname(self) -> str:
        return re.sub(r"[^A-Za-z0-9._-]+", "_",
                      self.settings.worker_name or "worker")

    def _checkpoint_dir(self) -> Path:
        if self.settings.checkpoint_dir:
            return Path(self.settings.checkpoint_dir).expanduser()
        from chiaswarm_tpu.node.settings import settings_root

        return settings_root() / "checkpoints" / self._spool_dirname()

    def _breaker_state_path(self) -> Path:
        spool = self._dead_letter_dir()
        # sibling FILE, not inside the spool: replay() globs *.json there
        return spool.parent / f"{spool.name}.breakers.json"

    def _dead_letter_dir(self) -> Path:
        if self.settings.dead_letter_dir:
            return Path(self.settings.dead_letter_dir).expanduser()
        from chiaswarm_tpu.node.settings import settings_root

        # namespaced by worker name: hermetic test workers (and multiple
        # workers sharing one settings root) must never replay — and then
        # DELETE — each other's spooled results
        return settings_root() / "dead_letter" / self._spool_dirname()

    def _shard_dead_letter_dir(self, index: int) -> Path:
        """Per-shard spool namespacing (swarmfed, ISSUE 17): shard 0
        keeps the historical directory (the breaker state file is its
        sibling, and single-hive workers never see a suffix); shards
        beyond it suffix the dirname so one shard's heal never replays
        — and then deletes — envelopes owed to another."""
        base = self._dead_letter_dir()
        if index <= 0:
            return base
        return base.parent / f"{base.name}__shard{index}"

    def _build_hive_shards(self, hive: Any | None) -> list[_HiveShard]:
        uris = self.settings.hive_uris() or [self.settings.hive_uri]
        if hive is not None:
            # an injected client (chaos/test seam) IS the control
            # plane: one bundle, whatever the settings say
            uris = uris[:1]
        shards: list[_HiveShard] = []
        for index, uri in enumerate(uris):
            client = hive if hive is not None else HiveClient(
                uri, self.settings.hive_token, self.settings.worker_name)
            # shard 0 keeps the historical backoff seed so single-hive
            # chaos schedules reproduce exactly; further shards
            # decorrelate from it AND from each other
            seed = (f"poll:{self.settings.worker_name}" if index == 0
                    else f"poll:{self.settings.worker_name}:{index}")
            shards.append(_HiveShard(
                index=index, uri=uri, client=client,
                session=HiveSession(
                    outage_after=self.settings.hive_outage_after,
                    name=f"shard{index}" if len(uris) > 1 else ""),
                spool=DeadLetterSpool(self._shard_dead_letter_dir(index)),
                backoff=Backoff(
                    base=self.settings.poll_backoff_base_s,
                    cap=self.settings.poll_backoff_cap_s,
                    seed=seed)))
        return shards

    async def _bootstrap_from_front(self) -> None:
        """Shard-list bootstrap (ISSUE 19 satellite, PR-17 residue):
        ``hive_front_uri`` names ONE federated front; the worker
        resolves it into the live shard uri list via ``GET
        /api/shards`` and rebuilds its session bundles from that —
        replacing any stale hand-configured list. An injected hive
        client (the chaos/test seam) always wins: it IS the control
        plane. Raises on an unreachable front: polling a guessed
        shard list would serve the wrong federation silently."""
        front = str(self.settings.hive_front_uri or "").strip()
        if not front or self._hive_injected:
            return
        from chiaswarm_tpu.node.federation import bootstrap_shard_uris

        uris = await bootstrap_shard_uris(front)
        if list(uris) == self.settings.hive_uris():
            return
        log.info("bootstrapped %d shard uri(s) from front %s",
                 len(uris), front)
        self.settings.hive_shard_uris = tuple(uris)
        self.settings.hive_uri = uris[0]
        self.shards = self._build_hive_shards(None)

    # single-hive compatibility surface: shard 0 IS the pre-federation
    # worker state (read-only views — nothing may rebind these)

    @property
    def hive(self) -> Any:
        return self.shards[0].client

    @property
    def hive_session(self) -> HiveSession:
        return self.shards[0].session

    @property
    def dead_letters(self) -> DeadLetterSpool:
        return self.shards[0].spool

    @property
    def _poll_backoff(self) -> Backoff:
        return self.shards[0].backoff

    @property
    def _last_hive_epoch(self) -> int | None:
        return self.shards[0].last_epoch

    def _default_pool(self) -> ChipPool:
        """One slot over all chips. An explicit ``mesh_shape`` setting
        wins; otherwise dp x tp derives from the device count and the
        heaviest catalog family (core/mesh.py::derive_mesh_spec) — a
        stock multi-chip node engages tensor parallelism exactly when a
        served model needs it, with no operator configuration."""
        from chiaswarm_tpu.core.mesh import MeshSpec, derive_mesh_spec

        if self.settings.mesh_shape:
            spec = MeshSpec(dict(self.settings.mesh_shape))
        else:
            spec = derive_mesh_spec(len(jax.devices()),
                                    self._heaviest_catalog_bytes(),
                                    latency=self.settings.latency_mode)
            log.info("derived default mesh: %s", spec.shape)
        return ChipPool(n_slots=1, mesh_spec=spec)

    def _heaviest_catalog_bytes(self) -> int | None:
        """Footprint of the heaviest model the catalog serves (None =
        empty catalog), feeding the default dp x tp mesh policy.

        MEASURED first (ISSUE 8): the residency ledger persists real
        per-model footprints across restarts (serving/residency.py), so
        a node that has served its catalog before derives its mesh from
        live numbers. Models never measured fall back to the bf16
        family estimate — the pre-ISSUE-8 knob, kept exactly for this
        no-model-has-loaded-yet case. Non-SD names (tts/audio/caption)
        fall through get_family to sd15 — a small, harmless overestimate
        that never turns tp on by itself."""
        try:
            from chiaswarm_tpu.models.configs import get_family
            from chiaswarm_tpu.pipelines.components import (
                estimate_family_bytes,
            )

            names = self.registry.known_models()
            if not names:
                return None
            residency = getattr(self.registry, "residency", None)
            measured = (residency.measured_footprints()
                        if residency is not None else {})
            heaviest = 0
            for name in names:
                nbytes = measured.get(name)
                if nbytes is None:
                    nbytes = estimate_family_bytes(get_family(name).name)
                heaviest = max(heaviest, int(nbytes))
            return heaviest or None
        except Exception as exc:  # policy must never block startup
            log.warning("mesh policy estimate failed (%s); using dp-only",
                        exc)
            return None

    # ---- lifecycle ----

    def startup(self) -> None:
        devices = jax.devices()
        if not devices:
            raise RuntimeError("no accelerator devices present; quitting")
        # this worker sells TPU time: any other backend is a start-up
        # error unless the operator NAMED it through jax's own variable
        # (JAX_PLATFORMS=cpu — dev hosts and the hermetic tests), so a
        # chip that failed to initialize can never serve quietly on CPU
        backend = jax.default_backend()
        named = [p.strip() for p in
                 (jax.config.jax_platforms or "").lower().split(",")]
        if backend != "tpu" and backend not in named:
            raise RuntimeError(
                f"no TPU: jax selected backend {backend!r} "
                f"({len(devices)} device(s)) and JAX_PLATFORMS="
                f"{jax.config.jax_platforms!r} does not name it; set "
                f"JAX_PLATFORMS={backend} to run a dev worker on it")
        from chiaswarm_tpu.node.settings import settings_root

        setup_logging(settings_root() / "logs", self.settings.log_filename,
                      self.settings.log_level)
        log.info("worker %s: %d device(s), %d slot(s), backend=%s",
                 self.settings.worker_name, len(devices), len(self.pool),
                 backend)
        # bf16 matmuls on the MXU — the TPU analog of the reference's
        # TF32/cudnn.benchmark startup knobs (swarm/worker.py:179-181)
        jax.config.update("jax_default_matmul_precision", "bfloat16")
        # amortize XLA compiles across worker restarts; a cache that
        # cannot be wired, or a codec that cannot build, fails HERE
        # rather than degrading every job after
        from chiaswarm_tpu import native
        from chiaswarm_tpu.core.compile_cache import (
            enable_persistent_compilation_cache,
        )

        log.info("persistent compile cache: %s",
                 enable_persistent_compilation_cache())
        native.load()

    def request_stop(self) -> None:
        self._stop.set()

    def _install_signal_handlers(self, loop) -> list:
        """SIGTERM/SIGINT trigger the graceful-drain path instead of
        killing in-flight paid chip time (settings gate for embedders)."""
        if not self.settings.install_signal_handlers:
            return []
        installed = []
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, self.request_stop)
                installed.append(sig)
            except (NotImplementedError, RuntimeError, ValueError):
                pass  # non-main thread / non-unix loop
        return installed

    @staticmethod
    def _remove_signal_handlers(loop, installed) -> None:
        for sig in installed:
            try:
                loop.remove_signal_handler(sig)
            except (NotImplementedError, RuntimeError, ValueError):
                pass

    def _replay_dead_letters(self, when: str = "startup",
                             shards: list[_HiveShard] | None = None
                             ) -> int:
        """Re-queue spooled results for upload. ``startup`` is the PR-2
        path (worker restarted under a hive outage); ``live`` is the
        ISSUE-14 ride-through — a hive (shard) healed mid-run, so ITS
        spool drains NOW instead of waiting for the next worker restart
        (swarmfed: a per-shard heal replays only that shard's spool —
        envelopes owed to a still-dead shard stay put). A file is only
        discarded after ITS upload succeeds (_deliver);
        ``_replayed_paths`` keeps a file that is already riding the
        result queue from enqueueing twice."""
        replayed = 0
        multiplexed = len(self.shards) > 1
        for shard in (self.shards if shards is None else shards):
            found = 0
            for path, result in shard.spool.replay():
                key = str(path)
                if key in self._replayed_paths:
                    continue  # already riding from an earlier replay
                self._replayed_paths.add(key)
                result["_dead_letter_path"] = key
                if multiplexed:
                    # route the replayed envelope to the shard whose
                    # spool held it (already stamped when its grant
                    # carried a shard key; stamped here for shutdown-
                    # spooled envelopes that never reached _deliver)
                    result.setdefault(HIVE_SHARD_KEY, shard.index)
                self.result_queue.put_nowait(result)
                self.stats.results_replayed += 1
                self._dl_replayed.inc(when=when)
                found += 1
            if found:
                log.warning("replaying %d dead-letter result(s) from %s "
                            "(%s)", found, shard.spool.directory, when)
            replayed += found
        return replayed

    # ---- hive-session bookkeeping (ISSUE 14; per-shard since 17) ----

    def _note_hive_ok(self, shard: _HiveShard | None = None) -> None:
        """A poll/upload/heartbeat reached this shard and succeeded; a
        heal drains the shard's dead-letter spool live — spooled chip
        time lands the moment the shard is back, no restart needed."""
        shard = shard if shard is not None else self.shards[0]
        if shard.session.note_success():
            log.warning(
                "hive%s healed after %.1fs outage; replaying its "
                "dead-letter spool live",
                f" shard {shard.index}" if len(self.shards) > 1 else "",
                shard.session.last_outage_s)
            self._replay_dead_letters(when="live", shards=[shard])

    def _note_hive_failure(self, source: str, exc: Exception,
                           shard: _HiveShard | None = None) -> None:
        """A poll/upload/heartbeat could not reach this shard. An HTTP
        4xx is excluded — the hive ANSWERED (a reference hive 404ing
        heartbeats must not read as an outage while polls succeed)."""
        if hive_reachable_error(exc):
            return
        shard = shard if shard is not None else self.shards[0]
        if shard.session.note_failure(source):
            # only THIS shard's leases are assumed lost: jobs owned by
            # the surviving shards keep their heartbeat coverage (the
            # blast-radius bound federation exists for)
            assumed = sum(
                1 for job_id in self._inflight
                if self._inflight_shard.get(job_id, 0) == shard.index)
            self.stats.hive_outages += 1
            if assumed:
                self.stats.leases_assumed_lost += assumed
            log.error(
                "hive%s OUTAGE after %d consecutive %s failure(s); %d "
                "in-flight lease(s) assumed lost — work rides through, "
                "results spool to dead-letter and replay on heal",
                f" shard {shard.index}" if len(self.shards) > 1 else "",
                shard.session.consecutive_failures, source, assumed)

    def _note_hive_epoch(self, raw: Any,
                         shard: _HiveShard | None = None) -> int | None:
        """Track the epoch stamped on a shard's grants/heartbeat acks;
        a bump means THAT shard recovered from its journal since we
        last spoke — every pre-bump lease it held is void (the
        recovered shard redelivers them), which the ride-through
        already assumed. Epochs are per-shard truth: shard 2 restarting
        must not void shard 1's leases."""
        try:
            epoch = None if raw is None else int(raw)
        except (TypeError, ValueError):
            return None
        if epoch is None:
            return None
        shard = shard if shard is not None else self.shards[0]
        previous = shard.last_epoch
        if previous is not None and epoch != previous:
            self.stats.hive_epoch_changes += 1
            log.warning("hive%s epoch %d -> %d: the hive recovered "
                        "from its journal; pre-recovery leases are void "
                        "and their jobs will redeliver",
                        f" shard {shard.index}"
                        if len(self.shards) > 1 else "",
                        previous, epoch)
        shard.last_epoch = epoch
        return epoch

    def _note_placement(self, raw: Any) -> None:
        """Feed a heartbeat ack's ``placement`` hint (swarmplan,
        ISSUE 19 — the fleet planner's model assignment for THIS
        worker) into the residency ledger: the next idle poll warms
        hinted models first, so placement shifts land before the
        traffic does. Malformed or absent hints are ignored — the
        hint is advisory, never load-bearing for correctness."""
        if not isinstance(raw, (list, tuple)) or not raw:
            return
        residency = getattr(self.registry, "residency", None)
        if residency is None:
            return
        try:
            residency.note_placement([str(m) for m in raw])
        except Exception:  # stub registries
            log.debug("placement hint dropped", exc_info=True)

    async def run(self) -> None:
        await self._bootstrap_from_front()
        self.startup()
        self._replay_dead_letters()
        # stale resume state from a previous run is superseded by the
        # hive's heartbeat-pushed copies (a redelivered job arrives WITH
        # its resume payload); leftovers would only shadow them
        self.checkpoints.clear()
        # bind the health endpoint BEFORE spawning workers: a port clash
        # must fail fast, not leave unsupervised poll/slot tasks running
        health_runner = await self._start_health_server()
        loop = asyncio.get_running_loop()
        signals = self._install_signal_handlers(loop)
        slot_tasks = [
            asyncio.create_task(self._slot_worker(slot), name=f"slot{i}")
            for i, slot in enumerate(self.pool)
        ]
        result_task = asyncio.create_task(self._result_worker(),
                                          name="results")
        # one poll loop per hive shard (swarmfed, ISSUE 17): each runs
        # its own backoff/outage state, so a dead shard slows only its
        # own loop while the rest keep feeding the work queue
        poll_tasks = [
            asyncio.create_task(self._poll_loop(shard),
                                name=(f"poll{shard.index}"
                                      if len(self.shards) > 1
                                      else "poll"))
            for shard in self.shards
        ]
        tasks = slot_tasks + [result_task] + poll_tasks
        if float(self.settings.heartbeat_s or 0) > 0:
            # heartbeats outlive the poll loop on purpose: they keep the
            # leases of draining in-flight jobs alive until the final
            # task cancellation below
            tasks.append(asyncio.create_task(self._heartbeat_loop(),
                                             name="heartbeat"))
        try:
            await self._stop.wait()
            await self._shutdown(poll_tasks, slot_tasks, result_task)
        finally:
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            # anything still queued embodies paid chip time: spool it
            self._spool_unsent_results()
            # refresh persisted breaker cooldowns (they survive restarts)
            self.breakers.save()
            if health_runner is not None:
                await health_runner.cleanup()
            self._remove_signal_handlers(loop, signals)

    async def _shutdown(self, poll_tasks, slot_tasks, result_task) -> None:
        """Graceful drain: polling halts first, in-flight slots finish,
        queued results upload — each phase bounded by its timeout so a
        wedged dependency cannot hold the process hostage."""
        log.info("stopping: polling halts; %d queued job(s) + in-flight "
                 "work drain, then %d pending result(s) upload",
                 self.work_queue.qsize(), self.result_queue.qsize())
        if not isinstance(poll_tasks, (list, tuple)):
            poll_tasks = [poll_tasks]
        for poll_task in poll_tasks:
            poll_task.cancel()
        await asyncio.gather(*poll_tasks, return_exceptions=True)
        self._draining.set()
        try:
            await asyncio.wait_for(
                asyncio.gather(*slot_tasks, return_exceptions=True),
                timeout=self.settings.drain_timeout_s)
        except asyncio.TimeoutError:
            log.error("slot drain exceeded %.0fs; cancelling in-flight "
                      "jobs (the hive recovers them via its timeout "
                      "detector)", self.settings.drain_timeout_s)
            for task in slot_tasks:
                task.cancel()
            await asyncio.gather(*slot_tasks, return_exceptions=True)
        # retire step-scheduler lanes: drained bursts already collected
        # their rows; anything still resident (abandoned executor threads
        # after a timed-out drain) fails over to the per-job path or an
        # envelope — rows are never silently dropped
        for slot in self.pool:
            stepper = getattr(slot, "_stepper", None)
            if stepper is not None:
                stepper.shutdown()
        try:
            await asyncio.wait_for(
                self.result_queue.join(),
                timeout=self.settings.result_drain_timeout_s)
        except asyncio.TimeoutError:
            log.error("result drain exceeded %.0fs; unsent results spool "
                      "to the dead-letter directory",
                      self.settings.result_drain_timeout_s)
        result_task.cancel()
        await asyncio.gather(result_task, return_exceptions=True)

    def _spool_unsent_results(self) -> None:
        """Shutdown durability: whatever the result worker never got to
        goes to disk, not to /dev/null."""
        while True:
            try:
                result = self.result_queue.get_nowait()
            except asyncio.QueueEmpty:
                return
            trace = obs_trace.detach(result)  # never serializes to disk
            if len(self.shards) > 1:
                # stamp the owner shard before serializing (the
                # _deliver path does this pre-upload; these envelopes
                # never got there) so the replay routes correctly
                owner = None
                if trace is not None:
                    owner = trace.meta.get(HIVE_SHARD_KEY)
                if owner is None:
                    owner = self._inflight_shard.get(result.get("id"))
                if owner is not None:
                    result.setdefault(HIVE_SHARD_KEY, int(owner))
            spooled = result.pop("_dead_letter_path", None)
            if spooled is None:  # replayed results already have a file
                self._result_shard(result).spool.spool(result)
                self.stats.results_dead_lettered += 1
            # same settling as _deliver's cancelled-upload path: a job
            # dead-lettered by shutdown still counts in jobs_total and
            # leaves its trace in the ring
            self._settle_inflight(result)
            self._finish_trace(trace, result, settled="dead_letter")
            self.result_queue.task_done()

    # ---- health endpoint (observability gap fix, SURVEY.md §5: the
    # reference's only health signal is the hive's timeout detection) ----

    def health(self) -> dict[str, Any]:
        from chiaswarm_tpu import WORKER_VERSION

        data = {
            "status": "ok",
            "worker_version": WORKER_VERSION,
            "worker_name": self.settings.worker_name,
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            "slots": len(self.pool),
            "jobs_done": self.jobs_done,
            "queue_depth": self.work_queue.qsize(),
            "results_pending": self.result_queue.qsize(),
            # degradation-ladder observability (node/resilience.py)
            "breakers": self.breakers.states(),
            "dead_letter_depth": sum(shard.spool.depth()
                                     for shard in self.shards),
            "poll_consecutive_errors": max(shard.backoff.failures
                                           for shard in self.shards),
            # fleet durability (ISSUE 6): resume-state spool + lease view
            "checkpoint_depth": self.checkpoints.depth(),
            "checkpoints_written": self.checkpoints.written,
            "checkpoints_corrupt_skipped": self.checkpoints.corrupt_skipped,
            "inflight_jobs": len(self._inflight),
            # hive-outage ride-through (ISSUE 14): the session state
            # machine + the last hive epoch seen — the edge-side view
            # of a hive incident and its journal recovery
            "hive_session": self.hive_session.snapshot(),
            "hive_epoch": self._last_hive_epoch,
            # swarmfed (ISSUE 17): the multiplexed view — one session/
            # epoch/spool entry per hive shard (a single-hive worker
            # shows its one shard; the keys above stay its aliases)
            "hive_shards": [
                {"shard": shard.index,
                 "uri": shard.uri,
                 "session": shard.session.snapshot(),
                 "hive_epoch": shard.last_epoch,
                 "dead_letter_depth": shard.spool.depth(),
                 "poll_consecutive_errors": shard.backoff.failures}
                for shard in self.shards
            ],
        }
        data.update(self.stats.snapshot())
        data["stepper"] = self._stepper_health()
        # gray-failure guard (ISSUE 10): device health, sickness
        # streaks, rung thresholds, quarantined devices — plus the
        # in-service chip count so a quarantine's capacity shrink is
        # visible next to the static device total
        data["guard"] = self.guard.snapshot()
        # swarmlens (ISSUE 11): the MEASURED hang-budget suggestion
        # derived from this process's chiaswarm_stepper_step_seconds
        # histogram — closes the "watchdog knobs are priors, not
        # measurements" carry-over: a real deployment reads its
        # suggested factor/floor/ceiling here
        data["guard"]["suggested_hang_budget"] = suggest_hang_budget()
        data["chips_in_service"] = sum(
            len(_slot_devices(slot)) or 1 for slot in self.pool)
        # overload control (ISSUE 9): admission-estimator state next to
        # the resilience stats — shed totals, brownout rung, EWMAs
        data["overload"] = dict(
            self.overload.snapshot(),
            enabled=bool(self.settings.overload_control))
        # HBM residency (ISSUE 8): the measured ledger + the one
        # authoritative per-model state enum (quarantine merged in)
        residency = getattr(self.registry, "residency", None)
        if residency is not None:
            data["residency"] = residency.snapshot()
        model_states = getattr(self.registry, "model_states", None)
        if callable(model_states):
            data["models"] = model_states()
        return data

    def _fleet_metrics(self) -> dict[str, Any]:
        """Compact per-worker snapshot the heartbeat pushes to the hive's
        fleet plane (ISSUE 13; served aggregated at ``GET /api/fleet``):
        demand (arrival EWMA), supply (lane occupancy, chips in
        service), state (overload, residency ledger) — the observed
        inputs the ROADMAP item-5 autoscaler closes its loop on. Cheap
        host dicts only; any failure degrades to a partial snapshot."""
        data: dict[str, Any] = {
            "queue_depth": self.work_queue.qsize(),
            "inflight_jobs": len(self._inflight),
            "jobs_done": self.jobs_done,
            "jobs_shed": self.stats.jobs_shed,
            "jobs_failed": self.stats.jobs_failed,
            "chips_in_service": sum(
                len(_slot_devices(slot)) or 1 for slot in self.pool),
        }
        try:
            stepper = self._stepper_health()
            data.update(
                arrival_rate_rows_s=float(
                    stepper.get("arrival_rate") or 0.0),
                lane_occupancy=float(
                    stepper.get("lane_occupancy") or 0.0),
                padding_waste=float(
                    stepper.get("padding_waste") or 0.0),
                lanes_live=int(stepper.get("lanes_live") or 0),
                step_seconds_ewma=float(
                    stepper.get("step_seconds_ewma") or 0.0))
        except Exception:  # lanes absent/stubbed: demand half missing
            pass
        try:
            data["overload"] = self.overload.fleet_view()
        except Exception:
            pass
        residency = getattr(self.registry, "residency", None)
        if residency is not None:
            try:
                snap = residency.snapshot()
                data["residency"] = {
                    "resident_models": len(
                        snap.get("resident_models") or ()),
                    "resident_bytes": snap.get("resident_bytes", 0),
                    "budget_bytes": snap.get("budget_bytes", 0),
                    "evictions": snap.get("evictions", 0),
                }
            except Exception:  # stub registries
                pass
        return data

    def _stepper_health(self) -> dict[str, Any]:
        """Step-scheduler counters next to the resilience stats: lane
        occupancy vs padding waste, rows spliced mid-flight, steps
        executed — the signals an operator tunes lane width by."""
        from chiaswarm_tpu.serving.stepper import (
            aggregate_stats,
            stepper_enabled,
        )

        steppers = [st for st in
                    (getattr(slot, "_stepper", None) for slot in self.pool)
                    if st is not None]
        data = {"enabled": stepper_enabled()}
        data.update(aggregate_stats(steppers))
        return data

    def _collect_metrics(self) -> None:
        """Scrape-time mirror of worker state the registry does not see
        increment-by-increment: queue depths, breaker states, and the
        stepper's lane stats (their sources keep their own monotonic
        totals; Prometheus collect-on-scrape copies them in)."""
        m = self.metrics
        m.gauge("chiaswarm_work_queue_depth",
                "jobs queued and not yet claimed by a slot").set(
            self.work_queue.qsize())
        m.gauge("chiaswarm_results_pending",
                "finished results waiting for upload").set(
            self.result_queue.qsize())
        m.counter("chiaswarm_jobs_done_total",
                  "jobs that completed execution on this worker").set_to(
            self.jobs_done)
        m.gauge("chiaswarm_dead_letter_depth",
                "result envelopes spooled on disk (all shard spools)").set(
            sum(shard.spool.depth() for shard in self.shards))
        m.gauge("chiaswarm_poll_consecutive_errors",
                "current poll-loop error streak (drives the backoff; "
                "worst shard)").set(
            max(shard.backoff.failures for shard in self.shards))
        # fleet durability (ISSUE 6): checkpoint spool + lease signals
        m.gauge("chiaswarm_checkpoint_depth",
                "in-flight resume checkpoints on disk").set(
            self.checkpoints.depth())
        m.counter("chiaswarm_checkpoints_written_total",
                  "lane/phase resume checkpoints written").set_to(
            self.checkpoints.written)
        m.counter("chiaswarm_checkpoints_corrupt_total",
                  "corrupt checkpoint files skipped loudly").set_to(
            self.checkpoints.corrupt_skipped)
        m.gauge("chiaswarm_inflight_jobs",
                "jobs between poll receipt and settled upload (the "
                "lease-heartbeat set)").set(len(self._inflight))
        # hive-outage ride-through (ISSUE 14): the session state gauge
        # next to the outage/assumed-lost counters ResilienceStats
        # already renders. Federated (ISSUE 17): the overall gauge
        # means "ANY shard in outage" (shard-0-equivalent at H=1) and
        # the labeled family carries the per-shard truth.
        obs_metrics.hive_session_state_gauge(self.metrics).set(
            1 if any(shard.session.in_outage for shard in self.shards)
            else 0)
        shard_gauge = obs_metrics.hive_shard_session_state_gauge(
            self.metrics)
        for shard in self.shards:
            shard_gauge.set(1 if shard.session.in_outage else 0,
                            shard=str(shard.index))
        # swarmsight (ISSUE 13): trace-ring eviction becomes a counter
        # so a slow scraper SEES that it lost spans (pair with the
        # /debug/traces?since= cursor instead of scraping faster)
        obs_metrics.trace_spans_evicted_counter(m).set_to(
            self.traces.spans_evicted)
        state_code = {"closed": 0, "half_open": 1, "open": 2}
        breaker_state = m.gauge(
            "chiaswarm_breaker_state",
            "per-model circuit breaker (0=closed 1=half-open 2=open)",
            labelnames=("model",))
        breaker_failures = m.gauge(
            "chiaswarm_breaker_consecutive_failures",
            "per-model consecutive breaker-counted failures",
            labelnames=("model",))
        for model, snap in self.breakers.states().items():
            breaker_state.set(state_code.get(snap["state"], 2), model=model)
            breaker_failures.set(snap["consecutive_failures"], model=model)
        stepper = self._stepper_health()
        counters = ("steps_executed", "rows_admitted",
                    "rows_admitted_midflight", "rows_completed",
                    "rows_expired", "rows_failed", "lanes_created",
                    "lanes_failed", "row_steps_active", "row_steps_padded",
                    "rows_resumed", "resumes_rejected",
                    "checkpoints_written", "lanes_evict_retired",
                    # swarmguard (ISSUE 10): condemnations, hung rows,
                    # poisoned rows, slow steps
                    "lanes_condemned", "rows_hung", "rows_invalid",
                    "steps_slow")
        for key in counters:
            m.counter(f"chiaswarm_stepper_{key}_total",
                      f"step scheduler: cumulative {key}").set_to(
                stepper.get(key, 0))
        gauges = ("lanes_live", "rows_active", "lane_rows_total",
                  "lane_occupancy", "padding_waste")
        for key in gauges:
            m.gauge(f"chiaswarm_stepper_{key}",
                    f"step scheduler: current {key}").set(
                stepper.get(key, 0))
        m.gauge("chiaswarm_stepper_enabled",
                "1 when CHIASWARM_STEPPER lane routing is on").set(
            1 if stepper.get("enabled") else 0)

    async def _start_health_server(self):
        port = int(self.settings.health_port or 0)
        if port <= 0 and not self.settings.health_bind_ephemeral:
            return None
        from aiohttp import web

        async def healthz(_request):
            return web.json_response(self.health())

        async def metrics_endpoint(_request):
            # worker-scoped metrics + the process-global registry
            # (compile cache, lane step timing) in one scrape body
            body = obs_metrics.render_all([self.metrics,
                                           obs_metrics.REGISTRY])
            return web.Response(
                body=body.encode("utf-8"),
                headers={"Content-Type": obs_metrics.CONTENT_TYPE})

        async def traces_endpoint(request):
            # ?since=<seq> is the scrape cursor (ISSUE 13): only traces
            # pushed after that ring sequence return, and the cursor
            # block tells the scraper whether eviction opened a gap
            # since its last visit (oldest_seq > since + 1)
            since = None
            if request.query.get("since"):
                try:
                    since = int(request.query["since"])
                except ValueError:
                    return web.json_response(
                        {"status": "error",
                         "error": "since must be an integer ring "
                                  "sequence number"}, status=400)
            cursor = self.traces.cursor()
            if request.query.get("format") == "tree":
                return web.json_response(
                    {"traces": self.traces.to_dicts(since),
                     "cursor": cursor})
            # default: chrome-tracing "complete" events — load the body
            # as-is at https://ui.perfetto.dev (the extra cursor key is
            # ignored by the viewer)
            doc = self.traces.to_chrome(since)
            doc["cursor"] = cursor
            return web.json_response(doc)

        async def numerics_endpoint(request):
            # swarmlens flight recorder (ISSUE 11): the bounded ring of
            # per-probe summaries, filterable by probe prefix; the
            # payload documents enablement so "empty because off" and
            # "empty because nothing tapped" read differently
            from chiaswarm_tpu.obs import numerics as obs_numerics

            limit = None
            try:
                if request.query.get("limit"):
                    limit = int(request.query["limit"])
            except ValueError:
                return web.json_response(
                    {"status": "error",
                     "error": "limit must be an integer"}, status=400)
            return web.json_response(obs_numerics.debug_payload(
                probe_prefix=request.query.get("probe") or None,
                limit=limit))

        async def profile_endpoint(request):
            try:
                seconds = float(request.query.get("seconds", "5"))
            except ValueError:
                return web.json_response(
                    {"status": "error", "error": "seconds must be a "
                     "number"}, status=400)
            out = request.query.get("dir") or None
            # capture blocks for the duration; keep the event loop free
            result = await asyncio.get_running_loop().run_in_executor(
                None, functools.partial(obs_profiling.capture,
                                        seconds, out))
            status = {"ok": 200, "busy": 409}.get(result.get("status"), 500)
            return web.json_response(result, status=status)

        app = web.Application()
        app.router.add_get("/healthz", healthz)
        app.router.add_get("/metrics", metrics_endpoint)
        app.router.add_get("/debug/traces", traces_endpoint)
        app.router.add_get("/debug/profile", profile_endpoint)
        app.router.add_get("/debug/numerics", numerics_endpoint)
        runner = web.AppRunner(app)
        await runner.setup()
        # loopback by default: the endpoint is operator observability,
        # not a service for arbitrary swarm peers
        host = self.settings.health_host or "127.0.0.1"
        site = web.TCPSite(runner, host, max(port, 0))
        await site.start()
        bound_port = runner.addresses[0][1] if runner.addresses else port
        self.health_address = (host, bound_port)
        log.info("health endpoints on %s:%d (/healthz /metrics "
                 "/debug/traces /debug/profile /debug/numerics)",
                 host, bound_port)
        return runner

    # ---- tasks ----

    async def _poll_loop(self, shard: _HiveShard | None = None) -> None:
        shard = shard if shard is not None else self.shards[0]
        async with aiohttp.ClientSession() as session:
            while not self._stop.is_set():
                # natural backpressure: wait for queue space — but keep
                # watching _stop, so a full queue can never stall shutdown
                while self.work_queue.full() and not self._stop.is_set():
                    try:
                        with obs_trace.span("poll.backpressure"):
                            await asyncio.wait_for(self._stop.wait(),
                                                   timeout=1.0)
                    except asyncio.TimeoutError:
                        pass
                if self._stop.is_set():
                    return
                # predictive backpressure (ISSUE 9): the queue-full wait
                # above only engages once the worker has ALREADY
                # over-committed a full queue of jobs it may then shed;
                # the overload controller throttles intake earlier, the
                # moment the queued backlog's drain estimate outruns the
                # backpressure budget
                if self.settings.overload_control:
                    throttle = self.overload.poll_throttle(
                        self.work_queue.qsize(), len(self.pool))
                    if throttle > 0:
                        self.stats.polls_backpressured += 1
                        try:
                            with obs_trace.span("poll.backpressure"):
                                await asyncio.wait_for(self._stop.wait(),
                                                       timeout=throttle)
                        except asyncio.TimeoutError:
                            pass
                        continue
                delay = await self._ask_for_work(session, shard)
                # self-healing ladder (ISSUE 10): apply any rungs the
                # device guard queued since the last poll — cache
                # flush, device quarantine (mesh shrink), restart
                self._apply_heal_rungs()
                try:
                    # poll_busy_s / poll_idle_s (or the error backoff):
                    # a job submitted now waits this out at the hive
                    with obs_trace.span("poll.delay"):
                        await asyncio.wait_for(self._stop.wait(),
                                               timeout=delay)
                except asyncio.TimeoutError:
                    pass

    async def _ask_for_work(self, session: aiohttp.ClientSession,
                            shard: _HiveShard | None = None) -> float:
        """One poll against one shard; returns the next delay. Errors
        back off exponentially with jitter (capped at hive.POLL_ERROR_S
        by default) and the schedule resets on the first successful
        poll. A federated shard's handout may include a STOLEN job —
        granted (and journaled) by a deeper-backlog peer; its payload
        carries that owner's shard index and epoch, so heartbeats and
        the upload route to the shard that actually holds the lease."""
        shard = shard if shard is not None else self.shards[0]
        t_poll = time.perf_counter()
        try:
            with obs_trace.span("poll.request"):
                jobs = await shard.client.get_work(session)
        except BadWorkerError as exc:
            # the hive ANSWERED (flagged us): reachable, not an outage
            self._note_hive_ok(shard)
            log.error("hive flagged this worker: %s", exc)
            return shard.backoff.next()
        except Exception as exc:
            self._note_hive_failure("poll", exc, shard)
            log.warning("poll failed: %s", exc)
            return shard.backoff.next()
        self._note_hive_ok(shard)
        shard.backoff.reset()
        poll_http_s = time.perf_counter() - t_poll
        if jobs:
            # poll-loop / step-boundary merge (ISSUE 7c): tell each
            # slot's resident step scheduler how many rows this poll is
            # about to format and submit, so adaptive lanes can grow at
            # their NEXT boundary instead of queueing the burst behind a
            # full lane. A hint only — never creates a scheduler.
            rows_hint = sum(
                max(1, int(job.get("num_images_per_prompt") or 1))
                for job in jobs)
            for slot in self.pool:
                stepper = getattr(slot, "_stepper", None)
                if stepper is not None:
                    stepper.note_poll(rows_hint)
        # brownout rung (ISSUE 9): refresh every slot's per-boundary
        # lane-admission cap on EVERY poll — entering brownout caps
        # promptly under load, and a cleared brownout lifts the cap on
        # the next (possibly idle) poll instead of lingering
        self._push_admission_caps()
        for job in jobs:
            if job.get("id") in self._inflight:
                # a lease-aware hive's starvation valve can redeliver a
                # job BACK to the worker still running it (every other
                # worker excluded). Running a second local copy would
                # orphan the heartbeat coverage of whichever copy
                # outlives the first settle (single id-keyed _inflight
                # entry) and churn the lease forever — drop the
                # duplicate; heartbeats re-hold the new lease and the
                # first run's upload settles it
                log.warning("job %s redelivered here while still in "
                            "flight; dropping the duplicate copy",
                            job.get("id"))
                self._inflight[job.get("id")] = time.monotonic()
                continue
            log.info("got job %s", job.get("id"))
            # the job's trace is born at hive receipt; its "poll" phase
            # covers the queue wait until a slot picks the job up (the
            # HTTP fetch itself rides as metadata — it served the whole
            # poll, not this one job). Redelivered jobs carry their
            # lineage: delivery attempt + the checkpoint step they
            # resume from (lease-aware hives, node/minihive.py).
            # ``queued_s`` (the hive's queue-age stamp) and ``attempt``
            # ride as root-span attributes on EVERY trace, so
            # /debug/traces answers "how stale was this job" without
            # the overload estimator being the only reader (ISSUE 13).
            resume = job.get("resume")
            ctx = job.pop(obs_flight.TRACE_CTX_KEY, None)
            # swarmfed (ISSUE 17): a federated grant names its OWNING
            # shard (a stolen job arrives via this shard's poll but its
            # lease, journal entry, and epoch all live on the owner).
            # Popped like the epoch stamp — never reaches argument
            # formatting — and rides the trace to the upload router.
            owner_raw = job.pop(HIVE_SHARD_KEY, None)
            try:
                owner_index = (shard.index if owner_raw is None
                               else int(owner_raw))
            except (TypeError, ValueError):
                owner_index = shard.index
            owner = (self.shards[owner_index]
                     if 0 <= owner_index < len(self.shards) else shard)
            # swarmdurable (ISSUE 14): the journaled hive's epoch stamp
            # is popped like the trace context (never reaches argument
            # formatting) and rides the trace to the upload, where the
            # envelope echoes it — the recovered hive's dedupe key.
            # Tracked against the OWNER: the epoch is that shard's
            # journal generation, whoever's poll delivered the grant.
            epoch = self._note_hive_epoch(
                job.pop(HIVE_EPOCH_KEY, None), owner)
            try:
                queued_s = max(0.0, float(job.get("queued_s") or 0.0))
            except (TypeError, ValueError):
                queued_s = 0.0
            trace = obs_trace.JobTrace(
                "job", id=job.get("id"),
                model=str(job.get("model_name") or ""),
                workflow=str(job.get("workflow") or ""),
                worker=self.settings.worker_name,
                attempt=job.get("attempt") or 1,
                queued_s=round(queued_s, 4),
                resume_step=(resume.get("step", 0)
                             if isinstance(resume, dict) else 0))
            if epoch is not None:
                trace.meta[HIVE_EPOCH_KEY] = epoch
            if owner_raw is not None:
                # only federated grants carry a shard; the meta stamp
                # routes the upload envelope to the owner (parity: an
                # un-federated grant stamps nothing anywhere)
                trace.meta[HIVE_SHARD_KEY] = owner.index
            if isinstance(ctx, dict) and ctx.get("trace_id"):
                # JOIN the hive's trace context (swarmsight, ISSUE 13):
                # this trace becomes the hive-granted attempt span's
                # child and the upload will carry a span digest for the
                # hive's flight record. With no context (reference
                # hive) the trace originates locally and the upload
                # payload keeps its historical shape — parity.
                trace.meta["trace_id"] = str(ctx.get("trace_id"))
                trace.meta["span_id"] = str(ctx.get("span_id") or "")
            trace.phase("poll", http_s=round(poll_http_s, 6))
            obs_trace.attach(job, trace)
            self._inflight[job.get("id")] = time.monotonic()
            self._inflight_shard[job.get("id")] = owner.index
            await self.work_queue.put(job)
        if jobs:
            return float(self.settings.poll_busy_s)
        # demand-driven prefetch (ISSUE 8): an empty poll is the ONLY
        # moment background warm loads may run — the ledger picks the
        # hottest evicted model (arrival EWMA) that fits the free budget
        # and loads it on a daemon thread; busy polls never trigger it
        if not self._stop.is_set() and self.work_queue.empty():
            residency = getattr(self.registry, "residency", None)
            if residency is not None:
                try:
                    residency.note_idle()
                except Exception as exc:  # prefetch must never stop polls
                    log.debug("residency prefetch tick failed: %s", exc)
        return float(self.settings.poll_idle_s)

    def _push_admission_caps(self) -> None:
        """Mirror the overload controller's brownout admission cap into
        every slot's resident step scheduler (None clears it)."""
        cap = (self.overload.admission_cap()
               if self.settings.overload_control else None)
        for slot in self.pool:
            stepper = getattr(slot, "_stepper", None)
            if stepper is not None:
                stepper.set_admission_cap(cap)

    # ---- the self-healing ladder (serving/guard.py, ISSUE 10) ----

    def _apply_heal_rungs(self) -> None:
        """Drain the device guard's queued ladder actions. The first
        rung (lane rebuild) is intrinsic to condemnation and already
        happened lane-side; this applies the worker-level escalations:

        - **cache_flush**: drop every cached executable — a sick
          device sometimes serves a corrupted compiled program; the
          next call recompiles fresh (``LruCache.drop_where``).
        - **device_quarantine**: shrink every slot's mesh to the
          healthy chips (data-axis meshes only — model-parallel slots
          cannot lose a chip and stay well-formed, so they escalate to
          restart instead). Capacity re-advertises through /healthz
          (``chips_in_service``) and the lane width bounds, which read
          the live ``slot.data_width``.
        - **restart**: request the graceful PR-2 drain and leave
          :data:`GUARD_RESTART_EXIT_CODE` for the supervisor — the
          "heal me by replacing me" rung of last resort.
        """
        if not self.settings.guard_enabled:
            return
        for action in self.guard.take_actions():
            if action.rung == "cache_flush":
                from chiaswarm_tpu.core.compile_cache import GLOBAL_CACHE
                from chiaswarm_tpu.serving.guard import note_cache_flush

                dropped = GLOBAL_CACHE.flush_executables()
                # re-cold every lane's hang budget: the recompiles this
                # flush causes must run under the ceiling, or the rung
                # would manufacture its own "hangs"
                note_cache_flush()
                log.error("guard heal: flushed %d cached executable(s) "
                          "(%s)", dropped, action.reason)
            elif action.rung == "device_quarantine":
                self._quarantine_device(action.device, action.reason)
            elif action.rung == "restart":
                log.error("guard heal: self-restart requested (%s); "
                          "draining gracefully, exit code %d",
                          action.reason, GUARD_RESTART_EXIT_CODE)
                self.exit_code = GUARD_RESTART_EXIT_CODE
                self.request_stop()

    def _quarantine_device(self, device: str, reason: str) -> None:
        """Shrink every slot mesh that contains ``device`` to its
        healthy chips. Lanes on the slot retire first (their rows
        bounce through the zero-loss fallback paths); fresh programs
        then build on the shrunk mesh. A slot that cannot shrink (its
        only chip, or a model-parallel mesh) logs and leaves the
        ladder to escalate."""
        from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh

        for slot in self.pool:
            mesh = getattr(slot, "mesh", None)
            if mesh is None:
                continue
            devices = list(mesh.devices.flatten())
            healthy = [d for d in devices if str(d.id) != str(device)]
            if len(healthy) == len(devices):
                continue  # this slot never held the sick chip
            shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            non_data = 1
            for name, size in shape.items():
                if name != "data":
                    non_data *= int(size)
            if not healthy or non_data != 1:
                log.error("guard heal: cannot quarantine device %s out "
                          "of slot %s (mesh %s); the ladder escalates "
                          "to restart instead", device,
                          getattr(slot, "index", "?"), shape)
                continue
            stepper = getattr(slot, "_stepper", None)
            if stepper is not None:
                # retire resident lanes: their device state is the last
                # holder of programs placed on the sick chip; unfinished
                # rows fail over to the per-job path (never lost)
                stepper.shutdown(timeout_s=5.0)
            slot.mesh = build_mesh(MeshSpec({"data": len(healthy)}),
                                   devices=healthy)
            log.error("guard heal: device %s quarantined (%s); slot %s "
                      "mesh shrunk to %d healthy chip(s) — capacity "
                      "re-advertised", device, reason,
                      getattr(slot, "index", "?"), len(healthy))

    async def _heartbeat_loop(self) -> None:
        """Lease keep-alive (ISSUE 6): every ``heartbeat_s``, tell the
        hive which jobs are in flight here and push their latest resume
        checkpoints (node/resilience.py spool; lanes write it at step
        boundaries). A hive that reassigned one of our leases answers
        with the lost ids — the local run keeps going (its result is
        deduped hive-side; first upload wins either way), but the loss
        is counted and logged so operators see lease churn."""
        interval = float(self.settings.heartbeat_s)
        # fleet-plane cadence (ISSUE 13): metric snapshots refresh at
        # most every ~2s — lease keep-alives can beat at 20 Hz in tests,
        # and re-serializing occupancy/residency state on every beat
        # would tax exactly the busy loops the plane observes. An
        # autoscaler reads seconds-scale state; 0 forces the next beat.
        # The throttle clock lives per shard (shard.last_metrics): each
        # shard serves its own /api/fleet slice of this worker.
        metrics_every = max(interval, 2.0)
        pushed: dict[Any, int] = {}  # job id -> spool version last pushed
        # leases the hive already told us it reassigned: count + warn
        # ONCE per loss, not once per beat for as long as the local run
        # keeps going (a 60s job at heartbeat_s=0.1 would otherwise
        # inflate leases_lost ~600x for a single reassignment)
        lost_reported: set[str] = set()

        def build_jobs(ids: list) -> list[dict]:
            # runs in a thread: checkpoint files are latent-sized, and a
            # synchronous read+parse per job per beat would stall the
            # event loop (polls, uploads, the health server). A None
            # checkpoint means "unchanged since my last beat" — the hive
            # keeps its stored copy, so skipping the re-push is free.
            jobs = []
            for job_id in ids:
                version = self.checkpoints.version(job_id)
                if (version is None or pushed.get(job_id) == version
                        or str(job_id) in lost_reported):
                    # a lost lease's checkpoint custody moved with the
                    # lease — the hive would reject the push as stale
                    jobs.append({"id": job_id, "checkpoint": None})
                    continue
                checkpoint = self.checkpoints.load(job_id)
                if checkpoint is not None:
                    pushed[job_id] = version
                jobs.append({"id": job_id, "checkpoint": checkpoint})
            return jobs

        async def idle_beat(shard: _HiveShard) -> None:
            # fleet plane (ISSUE 13): a worker with nothing in flight
            # ON THIS SHARD still pushes metrics-only beats (no jobs,
            # no lease bookkeeping) so its /api/fleet reads fresh
            # occupancy and capacity — an autoscaler must see idle
            # workers, not just busy ones — at the throttled metrics
            # cadence, not the lease cadence
            if time.monotonic() - shard.last_metrics < metrics_every:
                return
            idle_payload = {
                "worker_name": self.settings.worker_name,
                "jobs": [],
                "metrics": self._fleet_metrics(),
            }
            if shard.last_epoch is not None:
                idle_payload[HIVE_EPOCH_KEY] = shard.last_epoch
            try:
                ack = await shard.client.post_heartbeat(
                    session, idle_payload)
                self._note_hive_ok(shard)
                if isinstance(ack, dict):
                    self._note_hive_epoch(ack.get(HIVE_EPOCH_KEY), shard)
                    self._note_placement(ack.get("placement"))
                shard.last_metrics = time.monotonic()
            except Exception as exc:
                self._note_hive_failure("heartbeat", exc, shard)
                log.debug("idle heartbeat failed: %s", exc)

        async with aiohttp.ClientSession() as session:
            while True:
                await asyncio.sleep(interval)
                if self._stop.is_set() and not self._inflight:
                    return
                if not self._inflight:
                    pushed.clear()
                    lost_reported.clear()
                    for shard in self.shards:
                        await idle_beat(shard)
                    continue
                inflight = list(self._inflight)
                for job_id in [j for j in pushed if j not in self._inflight]:
                    pushed.pop(job_id, None)
                lost_reported &= {str(j) for j in inflight}
                # swarmfed (ISSUE 17): one beat per shard, each naming
                # only the jobs whose lease that shard OWNS (a stolen
                # job heartbeats to its owner, not the shard whose poll
                # delivered it) under that shard's own epoch handshake.
                # A dead shard fails only its own beat — the rest keep
                # their leases alive (per-shard outage independence).
                by_owner: dict[int, list] = {}
                for job_id in inflight:
                    by_owner.setdefault(
                        self._inflight_shard.get(job_id, 0),
                        []).append(job_id)
                reported: set[str] = set()
                any_beat_ok = False
                for shard in self.shards:
                    owned = by_owner.get(shard.index)
                    if not owned:
                        # nothing leased here: keep the shard's fleet
                        # plane fresh at the metrics cadence
                        await idle_beat(shard)
                        continue
                    payload = {
                        "worker_name": self.settings.worker_name,
                        "jobs": await asyncio.to_thread(
                            build_jobs, owned),
                    }
                    if shard.last_epoch is not None:
                        # the epoch handshake (ISSUE 14): a recovered
                        # hive rejects beats claiming a pre-restart
                        # epoch — the ack below hands back the current
                        # one, so the NEXT beat re-registers under it
                        payload[HIVE_EPOCH_KEY] = shard.last_epoch
                    if time.monotonic() - shard.last_metrics \
                            >= metrics_every:
                        # fleet plane (ISSUE 13): busy beats carry the
                        # metric snapshot at the same throttled
                        # cadence; the hive keeps the latest per worker
                        # at /api/fleet. Reference hives (no heartbeat
                        # endpoint) never see it — heartbeats are
                        # already off there.
                        payload["metrics"] = self._fleet_metrics()
                        shard.last_metrics = time.monotonic()
                    try:
                        response = await shard.client.post_heartbeat(
                            session, payload)
                        self._note_hive_ok(shard)
                        # a malformed 2xx body (non-dict JSON, non-list
                        # "lost") counts as a failed beat, NOT a loop
                        # exit: one bad proxy answer must never kill
                        # the keep-alive for the rest of the process
                        # lifetime
                        lost_raw = response.get("lost") or []
                        if not isinstance(lost_raw, list):
                            raise TypeError(
                                "non-list 'lost' in heartbeat "
                                f"response: {lost_raw!r}")
                        reported |= {str(j) for j in lost_raw}
                        self._note_hive_epoch(
                            response.get(HIVE_EPOCH_KEY), shard)
                        self._note_placement(response.get("placement"))
                        any_beat_ok = True
                    except Exception as exc:
                        # reference hives have no heartbeat endpoint,
                        # and a partitioned hive is exactly when we
                        # keep beating
                        self._note_hive_failure("heartbeat", exc, shard)
                        log.debug("heartbeat failed: %s", exc)
                if not any_beat_ok:
                    continue
                self.stats.lease_heartbeats += 1
                reported &= {str(j) for j in inflight}
                lost = sorted(reported - lost_reported)
                # REPLACE, don't accumulate: a job the hive stops
                # reporting lost was re-leased to us (starvation-valve
                # redelivery back to this worker) — checkpoint custody
                # returns, pushes resume, and a future loss warns anew
                lost_reported = reported
                if lost:
                    self.stats.leases_lost += len(lost)
                    log.warning("hive reassigned lease(s) for %s; local "
                                "work continues, upload will dedupe",
                                lost)

    async def _next_job(self) -> dict | None:
        """Block for the next queued job; returns None once the worker is
        draining AND the queue is empty (graceful-shutdown exit)."""
        if self._draining.is_set() and self.work_queue.empty():
            return None
        get_task = asyncio.ensure_future(self.work_queue.get())
        drain_task = asyncio.ensure_future(self._draining.wait())
        try:
            await asyncio.wait({get_task, drain_task},
                               return_when=asyncio.FIRST_COMPLETED)
            while not get_task.done():
                # draining with jobs still queued: claim them — but a
                # sibling slot may win the race for the last one, after
                # which this get can never be satisfied again (polling
                # already stopped), so re-check emptiness instead of
                # blocking the whole drain on it
                if self.work_queue.empty():
                    return None
                await asyncio.wait({get_task}, timeout=0.05)
            return get_task.result()
        finally:
            # no awaits between the queue checks above and these cancels,
            # and asyncio.Queue re-wakes the next getter when a woken one
            # is cancelled — a queued job can never be lost here
            get_task.cancel()
            drain_task.cancel()
            await asyncio.gather(get_task, drain_task,
                                 return_exceptions=True)

    async def _slot_worker(self, slot) -> None:
        """Feed one slot, keeping up to ``slot.depth`` jobs in flight.

        With depth 2, job N+1's host prep + program dispatch overlap job
        N's device->host image transfer (chip never idles between jobs);
        the slot's bounded semaphore enforces the cap, this semaphore
        just avoids pulling queue items nothing can run yet."""
        inflight = asyncio.Semaphore(max(1, getattr(slot, "depth", 1)))
        pending: set[asyncio.Task] = set()
        # cross-job coalescing: a dp-sharded slot runs up to dp compatible
        # jobs as ONE batched program (executor groups them; incompatible
        # jobs in a burst just run serially). 512px-class jobs
        # additionally batch up to single_chip_rows() per device and
        # 1024px-class stays at one row per device (a rule this chip
        # has not verified: core/compile_cache.py::single_chip_rows,
        # ROADMAP S1b). On multi-slot pools the drain loop below
        # additionally leaves ``_hungry_slots`` jobs in the queue, so a
        # coalescing slot never strips work an idle neighbor is already
        # waiting for.
        base_merge = slot.data_width

        async def run_burst(burst: list[dict]) -> None:
            try:
                results = await self._execute_burst(burst, slot)
                for result in results:
                    await self.result_queue.put(result)
                    self.jobs_done += 1
            except Exception as exc:
                # fault containment: a crash in the execution path must
                # never silently eat the burst (the reference's behavior —
                # the hive would wait out its deadline then flag the whole
                # worker); every job reports an explicit error envelope
                log.exception("slot worker error: %s", exc)
                kind = classify_exception(exc)
                outcomes: dict[str, set[str]] = {}
                for job in burst:
                    self.stats.jobs_failed += 1
                    outcomes.setdefault(
                        str(job.get("model_name") or ""), set()).add(kind)
                    envelope = error_result(job, exc, kind=kind)
                    trace = obs_trace.detach(job)
                    if trace is not None:  # ride on to the upload phase
                        obs_trace.attach(envelope, trace)
                    await self.result_queue.put(envelope)
                    self.jobs_done += 1
                self._record_outcomes(outcomes)
            finally:
                inflight.release()
                for _ in burst:
                    self.work_queue.task_done()

        held: dict | None = None  # mismatched drain candidate, runs next
        try:
            while True:
                await inflight.acquire()
                if held is not None:
                    burst, held = [held], None
                else:
                    # a slot that ALREADY has work in flight must not
                    # synchronously grab a job a hungry neighbor is
                    # blocked on (acquire+get both return without
                    # yielding when satisfiable, so at depth>=2 this
                    # slot would steal the fairness reserve before the
                    # woken neighbor's coroutine ever runs). Yield until
                    # the reserved jobs are consumed or surplus arrives.
                    while (pending and self._hungry_slots
                           and 0 < self.work_queue.qsize()
                           <= self._hungry_slots):
                        await asyncio.sleep(0)
                    self._hungry_slots += 1
                    try:
                        # the slot idle, waiting for a polled job
                        with obs_trace.span("slot.wait"):
                            job = await self._next_job()
                    finally:
                        self._hungry_slots -= 1
                    if job is None:  # draining and the queue is dry
                        inflight.release()
                        break
                    burst = [job]
                key = _burst_key(burst[0])
                rows = rows_max = job_rows(burst[0])
                per_device = single_chip_rows(burst[0])
                max_merge = base_merge * per_device
                while key is not None and len(burst) < max_merge:
                    # fairness reserve: jobs other slots are blocked on
                    # stay in the queue (the drain below has no awaits,
                    # so this count cannot change mid-drain)
                    if self.work_queue.qsize() <= self._hungry_slots:
                        break
                    try:
                        candidate = self.work_queue.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                    cand_rows = job_rows(candidate)
                    # num_images_per_prompt multiplies batch rows; never
                    # drain a burst whose total rows exceed what the
                    # heaviest member's solo run would put per device
                    # (the executor's _row_chunks is the authority, this
                    # avoids claiming jobs it would split anyway)
                    fits = rows + cand_rows <= rows_cap(
                        max(rows_max, cand_rows), base_merge, per_device)
                    if _burst_key(candidate) == key and fits:
                        burst.append(candidate)
                        rows += cand_rows
                        rows_max = max(rows_max, cand_rows)
                    else:
                        # hold the mismatch and run it as the NEXT burst:
                        # re-queueing at the tail would let it repeatedly
                        # lose its FIFO position to later-arriving
                        # coalescable jobs (unbounded reordering)
                        held = candidate
                        break
                task = asyncio.create_task(run_burst(burst))
                pending.add(task)
                task.add_done_callback(pending.discard)
            # graceful drain: in-flight bursts COMPLETE (and their results
            # reach the result queue) before this slot's task returns
            if pending:
                await asyncio.gather(*list(pending), return_exceptions=True)
        finally:
            # a held job was claimed from the queue but never dispatched;
            # put it back so cancellation cannot silently drop it (and
            # work_queue.join() accounting stays balanced)
            if held is not None:
                try:
                    self.work_queue.put_nowait(held)
                except asyncio.QueueFull:
                    log.error("dropping held job %s at shutdown: queue "
                              "full (hive recovers it via timeout)",
                              held.get("id"))
                self.work_queue.task_done()
            # forced-cancel path: cancel in-flight jobs, then AWAIT them
            # so their finally blocks (queue bookkeeping) run and no
            # pending task outlives the event loop
            for task in list(pending):
                task.cancel()
            if pending:
                await asyncio.gather(*list(pending), return_exceptions=True)

    # ---- execution with deadlines + the degradation ladder ----

    async def _attempt(self, jobs: list[dict], slot) -> list[dict]:
        """One executor call under the per-workflow deadline. A timed-out
        attempt yields explicit timeout envelopes — the hive hears about
        it NOW, not when its own worker-level detector fires. (The
        abandoned executor thread finishes in the background and its
        result is discarded; run_in_executor work is not interruptible.)
        """
        budget = max(self.settings.deadline_for(job.get("workflow"))
                     for job in jobs)
        for job in jobs:
            trace = obs_trace.job_trace(job)
            if trace is not None:
                # every member's "execute" phase spans this WHOLE
                # attempt (the burst runs as one call), so the service
                # EWMA must divide by the attempt size or a coalesced
                # burst teaches it N x the true per-job cost — and the
                # shed gate then sheds comfortably-servable jobs
                # (caught by review). Solo retries overwrite this to 1.
                trace.meta["attempt_jobs"] = len(jobs)
        executor = self._executor
        if len(jobs) == 1:
            dw = executor.do_work if executor is not None else do_work
            call = dw(jobs[0], slot, self.registry)
        else:
            dwb = (executor.do_work_batch if executor is not None
                   else do_work_batch)
            call = dwb(jobs, slot, self.registry)
        try:
            out = await asyncio.wait_for(call, timeout=budget)
        except asyncio.TimeoutError:
            self.stats.jobs_timed_out += len(jobs)
            # the estimator must learn the slowness a timeout proves:
            # the job burned at least the whole budget
            self.overload.note_service(jobs[0].get("workflow"), budget)
            log.error("burst %s exceeded its %.0fs deadline",
                      [job.get("id") for job in jobs], budget)
            return [error_result(
                job, f"job exceeded the node's {budget:.0f}s execution "
                     f"deadline", kind="timeout") for job in jobs]
        except Exception as exc:
            # the real executor renders its own failures as envelopes, so
            # anything raising THROUGH it is a genuine crash — contain it
            # at the job level with explicit envelopes (the reference
            # silently eats such jobs; the hive then times out the whole
            # worker, swarm/worker.py:92-97)
            log.exception("executor crashed on burst %s",
                          [job.get("id") for job in jobs])
            kind = classify_exception(exc)
            return [error_result(job, exc, kind=kind) for job in jobs]
        results = [out] if len(jobs) == 1 else list(out)
        # never let a miscounting executor silently drop a job
        while len(results) < len(jobs):
            results.append(error_result(
                jobs[len(results)], "executor returned no result for this "
                "job", kind="error"))
        return results

    async def _execute_burst(self, burst: list[dict], slot) -> list[dict]:
        """Run a burst through the degradation ladder:

        1. circuit-breaker gate — jobs for quarantined models get an
           immediate (non-fatal) refusal envelope, no chip time burned;
        2. one batched attempt under the deadline;
        3. jobs that failed transiently (image-fetch blip, device OOM)
           re-run SOLO with capped backoff + jitter — an OOM'd coalesced
           burst thereby splits and re-runs serially;
        4. final outcomes feed the per-model breakers.
        """
        results: list[dict | None] = [None] * len(burst)
        for job in burst:
            trace = obs_trace.job_trace(job)
            if trace is not None:  # poll phase ends, execute begins
                trace.phase("execute")
        ready: list[int] = []
        for i, job in enumerate(burst):
            model = str(job.get("model_name") or "")
            if model and not self.breakers.allow(model):
                self.stats.jobs_failed += 1
                self.stats.jobs_quarantined += 1
                # NOT fatal: this node refuses, another node may serve it
                results[i] = error_result(
                    job, f"model {model!r} is quarantined on this node "
                         f"(circuit breaker open)", kind="quarantined")
            else:
                ready.append(i)
        # deadline-aware admission (ISSUE 9): shed jobs the estimator
        # predicts would miss their deadline behind the local backlog —
        # BEFORE any chip time is spent. Sheds upload as non-fatal
        # "overloaded" envelopes (REDISPATCH_KINDS) and count as
        # capacity decisions, never failures.
        if ready and self.settings.overload_control:
            ready = self._shed_gate(burst, results, ready)
        if ready:
            attempt = await self._attempt([burst[i] for i in ready], slot)
            for i, result in zip(ready, attempt):
                results[i] = result
        max_retries = max(0, int(self.settings.transient_retries))
        outcomes: dict[str, set[str]] = {}
        for i in ready:
            kind = classify_result(results[i])
            for retry in range(1, max_retries + 1):
                if kind not in RETRYABLE_KINDS:
                    break
                delay = backoff_delay(retry, self.settings.retry_backoff_s,
                                      self.settings.retry_backoff_cap_s,
                                      self._retry_rng)
                log.warning("job %s hit a %s fault; solo re-run %d/%d "
                            "in %.2fs", burst[i].get("id"), kind, retry,
                            max_retries, delay)
                self.stats.jobs_retried += 1
                await asyncio.sleep(delay)
                results[i] = (await self._attempt([burst[i]], slot))[0]
                kind = classify_result(results[i])
            if kind != "ok":
                self.stats.jobs_failed += 1
            outcomes.setdefault(
                str(burst[i].get("model_name") or ""), set()).add(kind)
        self._record_outcomes(outcomes)
        # the trace hops from the consumed job dict onto its result
        # envelope so the upload phase (and finish) can find it
        for i, job in enumerate(burst):
            trace = obs_trace.detach(job)
            if trace is not None and results[i] is not None:
                obs_trace.attach(results[i], trace)
        return [result for result in results if result is not None]

    def _job_deadline_s(self, job: dict) -> float:
        """A job's end-to-end deadline budget: its own ``deadline_s``
        field (the swarmload harness attaches one per workload profile;
        the reference hive sends none), else the operator's per-model-
        FAMILY override (ISSUE 10 satellite — heavy families need more
        budget than their workflow's default; the harness derives
        suggested values from measured percentiles,
        node/loadgen.py::score_run), else the per-workflow setting."""
        raw = job.get("deadline_s")
        if raw is not None:
            try:
                value = float(raw)
                if value > 0:
                    return value
            except (TypeError, ValueError):
                pass
        table = self.settings.family_deadline_s or {}
        if table:
            family = self._model_family(job.get("model_name"))
            if family is not None and family in table:
                try:
                    value = float(table[family])
                    if value > 0:
                        return value
                except (TypeError, ValueError):
                    pass
        return self.settings.deadline_for(job.get("workflow"))

    @staticmethod
    def _model_family(model_name: Any) -> str | None:
        """Catalog family of a model name (None when unresolvable) —
        the key of the ``family_deadline_s`` override table."""
        if not model_name:
            return None
        try:
            from chiaswarm_tpu.models.configs import get_family

            return str(get_family(str(model_name)).name)
        except Exception:
            return None

    def _shed_gate(self, burst: list[dict], results: list,
                   ready: list[int]) -> list[int]:
        """Per-job admission verdicts for a burst about to execute;
        returns the indices that survive. Shed envelopes settle through
        the normal result path (exactly-once accounting unchanged)."""
        now = time.monotonic()
        stepper = self._stepper_health()
        step_ewma = float(stepper.get("step_seconds_ewma") or 0.0)
        queued = self.work_queue.qsize()
        slots = len(self.pool)
        admitted: list[int] = []
        for i in ready:
            job = burst[i]
            received = self._inflight.get(job.get("id"))
            # the job's age is hive queue time (the "queued_s" stamp a
            # lease-aware hive sends with each delivery — under
            # overload the backlog lives there) plus local queue wait
            try:
                queued_s = max(0.0, float(job.get("queued_s") or 0.0))
            except (TypeError, ValueError):
                queued_s = 0.0
            lane_estimate = None
            if stepper.get("enabled") and step_ewma > 0.0:
                try:
                    steps = int(job.get("num_inference_steps") or 0)
                except (TypeError, ValueError):
                    steps = 0
                if steps > 0:
                    lane_estimate = steps * step_ewma
            decision = self.overload.should_shed(
                workflow=job.get("workflow"),
                waited_s=queued_s + (0.0 if received is None
                                     else max(0.0, now - received)),
                deadline_s=self._job_deadline_s(job),
                # burst peers admitted ahead of this job are backlog
                # too — they left the work queue together, so qsize
                # alone undercounts exactly the jobs that will run
                # first (the 30-50 ms misses the harness caught)
                queued_ahead=queued + len(admitted), slots=slots,
                lane_estimate_s=lane_estimate)
            if not decision.shed:
                admitted.append(i)
                continue
            self.stats.jobs_shed += 1
            log.warning("job %s shed at admission: %s", job.get("id"),
                        decision.reason)
            results[i] = error_result(
                job, f"shed by overload control on this node "
                     f"({decision.reason}); a less-loaded node may "
                     f"still serve it", kind="overloaded")
        if len(admitted) < len(ready):
            # sheds may have tripped (or extended) brownout: cap lanes
            self._push_admission_caps()
        return admitted

    def _record_outcomes(self, outcomes: dict[str, set[str]]) -> None:
        """Feed the per-model circuit breakers, ONE record per model per
        burst: a single burst-level incident (e.g. a deadline expiry on
        an N-job coalesced burst) must count as one "consecutive"
        failure, not N — or one cold compile could quarantine a healthy
        model. Which kinds count is resilience.BREAKER_KINDS policy:
        model-load failures, timeouts, OOM that survived the ladder, and
        unclassified execution errors — NOT fatal user-input errors (K
        bad requests in a row must not quarantine a healthy model) and
        NOT transient network faults. A success for the model anywhere in
        the burst proves it serves and wins over same-burst failures."""
        for model, kinds in outcomes.items():
            if not model:
                continue
            if "ok" in kinds:
                self.breakers.record(model, ok=True)
            elif kinds & BREAKER_KINDS:
                self.breakers.record(model, ok=False)
            else:
                # says nothing about the model — but if this burst held
                # the half-open probe, free the slot for the next one
                self.breakers.record_inconclusive(model)

    # ---- result upload with durability ----

    async def _result_worker(self) -> None:
        async with aiohttp.ClientSession() as session:
            while True:
                result = await self.result_queue.get()
                try:
                    await self._deliver(session, result)
                finally:
                    self.result_queue.task_done()

    async def _deliver(self, session, result: dict) -> None:
        """A completed job's result embodies real chip time; a transient
        upload blip must not discard it (and a dropped result gets this
        worker flagged by the hive's timeout-based failure detection).
        Exhausted retries spool the envelope to the dead-letter directory
        for replay on the next startup."""
        trace = obs_trace.detach(result)  # must never reach json.dumps
        spooled = result.pop("_dead_letter_path", None)
        # lease attribution: a lease-aware hive partitions faults per
        # worker and dedupes redelivery races by uploader; the reference
        # hive ignores the extra field
        result.setdefault("worker_name", self.settings.worker_name)
        if trace is not None:
            # executor thread done -> this task running: loop wake-up,
            # outcome bookkeeping, the result queue
            trace.gap("result.wait")
            trace.phase("upload")
            # swarmdurable (ISSUE 14): echo the grant's hive-epoch
            # stamp so a recovered hive can tell a pre-crash grant's
            # upload (settled once as epoch salvage) from a live one.
            # Stamped BEFORE the upload attempts so a spooled envelope
            # keeps it — a dead-letter replay after the restart still
            # carries its original epoch. Never stamped when the hive
            # sent none: reference wire shape untouched.
            if trace.meta.get(HIVE_EPOCH_KEY) is not None:
                result.setdefault(HIVE_EPOCH_KEY,
                                  trace.meta[HIVE_EPOCH_KEY])
            # swarmfed (ISSUE 17): echo the grant's owner-shard stamp
            # the same way — the upload routes to the shard that holds
            # the lease (a stolen job's owner, not its delivery path),
            # and a spooled envelope keeps the routing for its replay.
            # Never stamped when the hive sent none: wire parity.
            if trace.meta.get(HIVE_SHARD_KEY) is not None:
                result.setdefault(HIVE_SHARD_KEY,
                                  trace.meta[HIVE_SHARD_KEY])
            if trace.meta.get("trace_id"):
                # swarmsight (ISSUE 13): a hive that stamped a trace
                # context gets the span digest back on the envelope —
                # the worker half of the cross-worker flight record.
                # Attached BEFORE the upload so a dead-lettered result
                # replays it later (straggler salvage keeps its story);
                # never attached without a context, so the reference-
                # hive wire shape is untouched.
                try:
                    result[obs_flight.SPAN_DIGEST_KEY] = \
                        obs_flight.span_digest(
                            trace, worker_name=self.settings.worker_name)
                except Exception as exc:  # telemetry must never block
                    log.debug("span digest failed for %s: %s",
                              result.get("id"), exc)
        shard = self._result_shard(result)
        try:
            with obs_trace.activate(trace):
                uploaded = await self._upload_with_retry(session, result,
                                                         shard)
        except asyncio.CancelledError:
            # shutdown cancelled us mid-upload: persist before dying
            if spooled is None:
                shard.spool.spool(result)
                self.stats.results_dead_lettered += 1
            self._settle_inflight(result)
            self._finish_trace(trace, result, settled="dead_letter")
            raise
        if uploaded:
            if spooled is not None:
                shard.spool.discard(spooled)
                self._replayed_paths.discard(str(spooled))
            # GC on ack (ISSUE 6 satellite): the job settled, its resume
            # checkpoint is stale by definition
            self.checkpoints.discard(result.get("id"))
        elif spooled is None:
            shard.spool.spool(result)
            self.stats.results_dead_lettered += 1
        else:
            # a replayed result that failed again keeps its existing
            # file — and leaves the in-queue set, so the NEXT heal's
            # live replay picks it up again
            self._replayed_paths.discard(str(spooled))
        self._settle_inflight(result)
        self._finish_trace(trace, result,
                           settled="uploaded" if uploaded else "dead_letter")

    def _result_shard(self, result: dict) -> _HiveShard:
        """Which shard an upload belongs to: the envelope's owner-shard
        echo first (stamped from the grant; survives spool + replay),
        the in-flight routing table second, shard 0 otherwise (the
        single-hive worker always lands here)."""
        raw = result.get(HIVE_SHARD_KEY)
        if raw is None:
            raw = self._inflight_shard.get(result.get("id"))
        try:
            index = 0 if raw is None else int(raw)
        except (TypeError, ValueError):
            index = 0
        if 0 <= index < len(self.shards):
            return self.shards[index]
        return self.shards[0]

    def _settle_inflight(self, result: dict) -> None:
        """The job left this worker's hands (uploaded or dead-lettered):
        stop heartbeating its lease."""
        self._inflight.pop(result.get("id"), None)
        self._inflight_shard.pop(result.get("id"), None)

    def _finish_trace(self, trace, result: dict, settled: str) -> None:
        """Close a job's span tree, publish it to the worker's trace
        ring, and fold its phase durations into the latency histograms
        — the per-job numbers the ROADMAP's perf work tunes against."""
        if trace is None:
            return
        outcome = classify_result(result)
        trace.meta["outcome"] = outcome
        trace.meta["settled"] = settled
        trace.finish(self.traces)
        service_s = 0.0
        for phase in trace.root.children:
            self._phase_seconds.observe(phase.duration_s, phase=phase.name)
            if phase.name in ("execute", "upload"):
                service_s += phase.duration_s
        self._job_seconds.observe(trace.root.duration_s)
        self._jobs_total.inc(outcome=outcome)
        if outcome == "ok" and service_s > 0.0:
            # the admission estimator's service EWMA (node/overload.py)
            # learns the worker-side cost of a successful job — execute
            # + upload, queue wait excluded (the queue-drain term
            # models that separately), divided by the attempt size its
            # execute phase spanned (see _attempt). Failure envelopes
            # are excluded: a fast refusal would drag the estimate
            # toward zero and re-admit exactly the jobs being shed.
            try:
                attempt_jobs = max(1, int(
                    trace.meta.get("attempt_jobs") or 1))
            except (TypeError, ValueError):
                attempt_jobs = 1
            self.overload.note_service(trace.meta.get("workflow"),
                                       service_s / attempt_jobs)

    async def _upload_with_retry(self, session, result,
                                 shard: _HiveShard | None = None) -> bool:
        shard = shard if shard is not None else self.shards[0]
        retries = max(1, int(self.settings.upload_retries))
        for attempt in range(1, retries + 1):
            try:
                response = await shard.client.post_result(session, result)
                self._note_hive_ok(shard)
                log.info("uploaded result %s: %s", result.get("id"),
                         response)
                return True
            except Exception as exc:
                self._note_hive_failure("upload", exc, shard)
                self.stats.upload_retries += 1
                log.warning("result upload attempt %d/%d failed: %s",
                            attempt, retries, exc)
                if shard.session.in_outage:
                    # ride-through (ISSUE 14): during a declared outage
                    # the full retry ladder only delays the spool (and
                    # the next result behind it). One probe per result
                    # keeps testing the hive; the spool replays LIVE on
                    # heal, so giving up early costs nothing.
                    log.warning("hive in outage; spooling result %s "
                                "after a single attempt",
                                result.get("id"))
                    return False
                if attempt < retries:
                    await asyncio.sleep(backoff_delay(
                        attempt, self.settings.upload_retry_delay_s,
                        self.settings.poll_backoff_cap_s,
                        self._retry_rng))
        return False


async def run_worker(settings: Settings | None = None) -> int:
    """Run one worker to completion; returns its exit code — 0, or
    guard.GUARD_RESTART_EXIT_CODE when the self-healing ladder's
    restart rung requested a supervisor-visible restart (ISSUE 10)."""
    worker = Worker(settings)
    await worker.run()
    return int(worker.exit_code)


def main() -> None:  # `python -m chiaswarm_tpu.node.worker`
    import sys

    sys.exit(asyncio.run(run_worker()))


if __name__ == "__main__":
    main()
