"""Job executor: async -> blocking bridge with error-as-artifact semantics.

Capability parity with swarm/generator.py:12-95:

- ``do_work`` hops from the event loop to a worker thread so generation
  never blocks polling/uploads (reference: loop.run_in_executor, :12-14).
- Error taxonomy drives hive retry behavior: argument-formatting errors and
  ``ValueError`` raised by callbacks are **fatal** (``fatal_error: True`` —
  the job's inputs are bad, do not redispatch, :34-41,:56-63); any other
  exception returns an error artifact *without* the fatal flag so the hive
  may retry elsewhere (:65-79).
- Every failure renders as an artifact (image or JSON by requested
  content type) so the user always receives a result object.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Any

import numpy as np

from chiaswarm_tpu import WORKER_VERSION
from chiaswarm_tpu.core.compile_cache import single_chip_rows
from chiaswarm_tpu.node.job_args import format_args
from chiaswarm_tpu.node.output_processor import (
    encode_image,
    image_from_text,
    make_result,
    make_text_result,
)
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.resilience import (
    NONFATAL_KINDS,
    checkpoint_scope,
    classify_exception,
)
from chiaswarm_tpu.obs import trace as obs_trace
from chiaswarm_tpu.node.hivelog import HIVE_EPOCH_KEY
from chiaswarm_tpu.obs.flight import TRACE_CTX_KEY
from chiaswarm_tpu.obs.profiling import job_profile
from chiaswarm_tpu.obs.trace import span

log = logging.getLogger("chiaswarm.executor")


async def do_work(job: dict[str, Any], slot, registry: ModelRegistry) -> dict:
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(
        None, synchronous_do_work, job, slot, registry
    )


async def do_work_batch(jobs: list[dict[str, Any]], slot,
                        registry: ModelRegistry) -> list[dict]:
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(
        None, synchronous_do_work_batch, jobs, slot, registry
    )


def _error_payload(exc: Exception, content_type: str,
                   kind: str | None = None) -> tuple[dict, dict]:
    message = exc.args[0] if exc.args else "error generating result"
    message = str(message)
    # structured envelope: the failure kind + exception class ride in the
    # config so the hive (and the worker's own degradation ladder,
    # node/worker.py) learn of failures explicitly instead of via the
    # hive's timeout detector (swarm/worker.py:92-97)
    config = {
        "error": message,
        "error_kind": kind or classify_exception(exc),
        "error_class": type(exc).__name__,
    }
    if content_type.startswith("image/"):
        img = image_from_text(message)
        artifacts = {
            "primary": make_result(encode_image(img, content_type),
                                   content_type)
        }
    else:
        artifacts = {"primary": make_text_result(message)}
    return artifacts, config


def _result(job_id: Any, artifacts: dict, config: dict,
            fatal: bool = False) -> dict[str, Any]:
    result = {
        "id": job_id,
        "artifacts": artifacts,
        "nsfw": config.get("nsfw", False),
        "worker_version": WORKER_VERSION,
        "pipeline_config": config,
    }
    if fatal:
        result["fatal_error"] = True
    return result


def error_result(job: dict[str, Any], exc_or_message: Any, *,
                 kind: str | None = None, fatal: bool = False) -> dict:
    """Structured error envelope for a job that never produced a result
    through the normal executor path — deadline expiry, a crashed slot
    task, a circuit-breaker refusal (node/worker.py), or a chaos-injected
    executor fault (node/chaos.py). Same wire shape as executor-internal
    failures, so the hive's result handler needs no new cases."""
    if isinstance(exc_or_message, BaseException):
        exc: Exception = exc_or_message if isinstance(
            exc_or_message, Exception) else RuntimeError(str(exc_or_message))
    else:
        exc = RuntimeError(str(exc_or_message))
    content_type = str(job.get("content_type") or "image/jpeg")
    artifacts, config = _error_payload(exc, content_type, kind=kind)
    return _result(job.get("id"), artifacts, config, fatal=fatal)


# per-job XLA tracing when CHIASWARM_PROFILE_DIR is set — the hook the
# reference lacks entirely (SURVEY.md §5: its only telemetry is print
# statements). Traces open in XProf/Perfetto. Now shared with the
# worker's on-demand /debug/profile capture, which holds the same
# process-global profiler lock (chiaswarm_tpu/obs/profiling.py).
_maybe_profile = job_profile


def _format(job: dict[str, Any], registry: ModelRegistry):
    """-> (job_id, content_type, callback, kwargs) or a fatal result."""
    job = dict(job)
    job.pop(obs_trace.TRACE_KEY, None)  # never a pipeline kwarg
    # the hive's trace context and epoch stamp are normally popped at
    # poll receipt (node/worker.py); strip them defensively for
    # directly-injected jobs (tests, resubmissions) — like the trace
    # itself, never a kwarg
    job.pop(TRACE_CTX_KEY, None)
    job.pop(HIVE_EPOCH_KEY, None)
    job_id = job.pop("id", None)
    content_type = job.get("content_type", "image/jpeg")
    try:
        with span("format"):
            callback, kwargs = format_args(job, registry)
    except Exception as exc:
        # bad inputs are fatal (do not redispatch) — but formatting also
        # FETCHES input images, and a network blip is not the user's
        # fault: transient kinds upload without the fatal flag so the
        # worker's ladder (and failing that, the hive) may retry, and a
        # node-local model-unavailable is a ROUTING problem a lease-aware
        # hive redispatches (resilience.REDISPATCH_KINDS), never fatal
        kind = classify_exception(exc)
        fatal = kind not in NONFATAL_KINDS
        log.warning("job %s failed formatting (%s): %s", job_id, kind, exc)
        artifacts, config = _error_payload(exc, content_type, kind=kind)
        return None, _result(job_id, artifacts, config, fatal=fatal)
    return (job_id, content_type, callback, kwargs), None


def _execute(job_id, content_type, callback, kwargs, slot) -> dict:
    from chiaswarm_tpu.serving.guard import (
        InvalidOutput,
        _slot_devices,
        watch_solo,
    )

    # swarmguard (ISSUE 10): the solo denoise phase runs under the hang
    # watchdog (budget = steps x the lane step EWMA x k; never armed
    # cold, so a first-call compile cannot false-positive). DIFFUSION
    # callbacks only — the step EWMA is a diffusion-lane signal and
    # says nothing about video/audio/caption service times. A
    # hung-but-returned call raises StepHung -> classified transient ->
    # the PR-2 ladder re-runs it; one that never returns is the
    # deadline envelope's job (node/worker.py).
    watched_steps = (kwargs.get("num_inference_steps")
                     if getattr(callback, "__name__", "")
                     == "diffusion_callback" else None)
    # warmth key ~ the solo program variant: a new model or resolution
    # compiles its own executable, and its first call must get the
    # ceiling budget, not another variant's steady-state one
    watch_key = (str(kwargs.get("model_name")), kwargs.get("height"),
                 kwargs.get("width"))
    try:
        with _maybe_profile(job_id), \
                watch_solo(slot, watched_steps, key=watch_key):
            artifacts, config = slot(callback, **kwargs)
    except InvalidOutput as exc:
        # numerically poisoned output screened before upload: a
        # non-fatal invalid_output envelope (REDISPATCH_KINDS) instead
        # of garbage pixels, and a health event for this slot's devices
        guard = getattr(slot, "_guard", None)
        if guard is not None:
            guard.note_invalid_output(
                _slot_devices(slot),
                model=str(kwargs.get("model_name") or ""))
        log.error("job %s produced invalid output (%s); envelope "
                  "uploaded instead of the poisoned image", job_id, exc)
        artifacts, config = _error_payload(exc, content_type,
                                           kind="invalid_output")
        return _result(job_id, artifacts, config)
    except ValueError as exc:  # callback-declared unrecoverable input error
        # ...EXCEPT a node-local model-unavailable (missing/broken/
        # quarantined checkpoint): that is this node refusing, not the
        # inputs being bad — it uploads WITHOUT the fatal flag so a
        # lease-aware hive redispatches it to a node that holds the
        # model (ISSUE 6; resolves the PR-2 taxonomy tension)
        kind = classify_exception(exc)
        fatal = kind not in NONFATAL_KINDS
        log.warning("job %s %s: %s", job_id,
                    "fatal" if fatal else kind, exc)
        artifacts, config = _error_payload(exc, content_type, kind=kind)
        return _result(job_id, artifacts, config, fatal=fatal)
    except Exception as exc:  # error artifact without the fatal flag: the
        log.exception("job %s errored", job_id)  # hive may retry elsewhere
        artifacts, config = _error_payload(exc, content_type)
        return _result(job_id, artifacts, config)
    return _result(job_id, artifacts, config)


def _stepper_submit(job_id, content_type, callback, kwargs, slot,
                    registry):
    """Submit an eligible diffusion job (txt2img / img2img / inpaint /
    ControlNet, ISSUE 7) to the slot's continuous step scheduler
    (serving/stepper.py). Returns a ticket or None (run the job through
    the ordinary burst/solo path instead). Submission failures are
    never terminal for the job — it just falls back."""
    from chiaswarm_tpu.workloads.diffusion import (
        diffusion_callback,
        stepper_eligible,
        stepper_submit,
    )

    if callback is not diffusion_callback or not stepper_eligible(kwargs):
        return None
    # residency fast-path (ISSUE 8): a model the ledger knows is
    # degraded to load-per-job must not pin a lane resident — and must
    # not pay a full transient load just to be rejected by the lane
    # (workloads.stepper_submit re-checks after first-ever loads)
    lane_ok = getattr(registry, "lane_resident_ok", None)
    if callable(lane_ok) and not lane_ok(str(kwargs.get("model_name"))):
        log.debug("job %s model degraded to load-per-job; skipping lanes",
                  job_id)
        return None
    from chiaswarm_tpu.core.rng import draw_seed
    from chiaswarm_tpu.serving.stepper import LaneReject

    seed = kwargs.get("seed")
    seed = draw_seed() if seed is None else int(seed)
    try:
        return stepper_submit(slot, registry, kwargs, seed, job_id=job_id)
    except LaneReject as exc:
        log.debug("job %s not lane-eligible (%s)", job_id, exc)
        return None
    except Exception as exc:
        log.warning("job %s lane submit failed (%s); per-job path",
                    job_id, exc)
        return None


def _stepper_collect(job_id, content_type, slot, ticket,
                     registry=None, kwargs=None) -> dict | None:
    """Wait out a lane ticket. Returns the finished result, a timeout
    envelope (in-lane deadline expiry), an ``invalid_output`` envelope
    (poisoned row, swarmguard), or None — meaning the job must re-run
    through the per-job path (lane fault; zero-loss fallback).

    When ``kwargs`` is provided and the lane was CONDEMNED by the hang
    watchdog (guard.LaneHung), the job is re-admitted ONCE to a freshly
    built lane, resuming from the condemnation checkpoint — the
    self-healing lane-rebuild rung. A second hang (or a reject) falls
    through to the per-job path, the PR-2 ladder."""
    from chiaswarm_tpu.serving.guard import (
        InvalidOutput,
        LaneHung,
        _slot_devices,
    )
    from chiaswarm_tpu.serving.stepper import LaneDeadline
    from chiaswarm_tpu.workloads.diffusion import stepper_finish

    try:
        artifacts, config = stepper_finish(ticket)
    except LaneDeadline as exc:
        return error_result({"id": job_id, "content_type": content_type},
                            exc, kind="timeout")
    except InvalidOutput as exc:
        guard = getattr(slot, "_guard", None)
        if guard is not None:
            guard.note_invalid_output(_slot_devices(slot),
                                      model=str(ticket.model_name))
        log.error("job %s retired invalid_output (%s); envelope "
                  "uploaded instead of a poisoned image", job_id, exc)
        return error_result({"id": job_id, "content_type": content_type},
                            exc, kind="invalid_output")
    except LaneHung as exc:
        # hang accounting (device health, condemned-lane counters)
        # already happened lane-side when the watchdog condemned it
        if kwargs is not None:
            healed = _stepper_resubmit(job_id, content_type, slot,
                                       registry, kwargs, ticket, exc)
            if healed is not None:
                return healed
        log.warning("job %s lost its lane to the watchdog (%s); "
                    "per-job path", job_id, exc)
        return None
    except Exception as exc:
        kind = classify_exception(exc)
        if kind == "oom":
            from chiaswarm_tpu.serving.stepper import get_stepper

            get_stepper(slot).note_oom()  # rebuild lanes narrower
        log.warning("job %s lane run failed (%s: %s); per-job path",
                    job_id, kind, exc)
        return None
    return _result(job_id, artifacts, config)


def _stepper_resubmit(job_id, content_type, slot, registry, kwargs,
                      ticket, exc) -> dict | None:
    """Re-admit a condemned lane's job to a freshly built lane
    (swarmguard lane-rebuild rung): same kwargs, the SAME seed the
    first admission drew (a resumed trajectory must not re-derive its
    noise), and the condemnation checkpoint as the resume payload so
    surviving rows splice back in at step k instead of restarting.
    Returns the finished result or None (fall back to the per-job
    path). The inner collect passes no kwargs — a second hang is not
    healed again."""
    from chiaswarm_tpu.workloads.diffusion import stepper_submit

    retry_kwargs = dict(kwargs)
    retry_kwargs["seed"] = ticket.seed
    resume = getattr(exc, "resume", None)
    if isinstance(resume, dict):
        retry_kwargs["resume"] = resume
    else:
        retry_kwargs.pop("resume", None)
    try:
        retry = stepper_submit(slot, registry, retry_kwargs, ticket.seed,
                               job_id=job_id)
    except Exception as submit_exc:
        log.warning("job %s lane re-admission failed (%s); per-job "
                    "path", job_id, submit_exc)
        return None
    log.warning("job %s re-admitted to a fresh lane after condemnation"
                "%s", job_id,
                (f", resuming at step {resume.get('step')}"
                 if isinstance(resume, dict) else " (no checkpoint — "
                 "restarting at step 0)"))
    return _stepper_collect(job_id, content_type, slot, retry)


def synchronous_do_work(job: dict[str, Any], slot,
                        registry: ModelRegistry) -> dict[str, Any]:
    log.info("processing job %s", job.get("id"))
    # the job's span tree follows it into this thread: format / encode /
    # step / decode spans below attach under the worker's open
    # "execute" phase (chiaswarm_tpu/obs/trace.py). The checkpoint scope
    # binds the worker's spool so the solo path can record its coarse
    # phase markers (workloads/diffusion.py; lanes snapshot themselves).
    trace = obs_trace.job_trace(job)
    if trace is not None:
        # execute phase start -> this thread running: the hand-over
        trace.gap("handover")
    with obs_trace.activate(trace), \
            checkpoint_scope(getattr(slot, "_checkpoint_spool", None),
                             job.get("id")):
        formatted, fatal = _format(job, registry)
        if formatted is None:
            return fatal
        job_id, content_type, _, kwargs = formatted
        ticket = _stepper_submit(*formatted, slot, registry)
        if ticket is not None:
            result = _stepper_collect(job_id, content_type, slot, ticket,
                                      registry, kwargs)
            if result is not None:
                return result
        return _execute(*formatted, slot)


def _coalesce_key(kwargs: dict[str, Any]):
    from chiaswarm_tpu.workloads.diffusion import COALESCE_KEYS

    # img2img/inpaint coalesce only with matching modes AND pixel grids:
    # the height/width kwargs may be absent for image jobs (the callback
    # takes the image's own size), so key on the fetched image AND mask
    # shapes (mask sizes are free-form solo — the pipeline resizes — so
    # presence alone would group unstackable masks)
    image = kwargs.get("image")
    mask = kwargs.get("mask_image")
    return ((kwargs.get("model_name"),
             None if image is None else tuple(np.asarray(image).shape),
             None if mask is None else tuple(np.asarray(mask).shape))
            + tuple(repr(kwargs.get(k)) for k in COALESCE_KEYS))


def job_rows(job_or_kwargs: dict[str, Any]) -> int:
    """Batch rows one job contributes to a coalesced program
    (``num_images_per_prompt`` multiplies rows; a bad value surfaces per
    job downstream, not here). Shared by this module's chunking and the
    worker's drain (node/worker.py) so the two never drift."""
    try:
        return max(1, int(job_or_kwargs.get("num_images_per_prompt") or 1))
    except (TypeError, ValueError):
        return 1


def rows_cap(rows_max: int, data_width: int, per_device_rows: int = 1) -> int:
    """Max total rows a coalesced program may carry:
    dp * max(ceil(rows_max/dp), per_device_rows) — per device, the LARGER
    of the heaviest member's own solo footprint and the measured
    profitable batch, never their product (a multi-image 512px job must
    not multiply into 4x its solo per-device memory; rows past the
    plateau add no throughput anyway)."""
    dw = max(1, int(data_width))
    return dw * max(-(-rows_max // dw), max(1, int(per_device_rows)))


def _row_chunks(group: list, data_width: int) -> list[list]:
    """Split a compatible group so one batched program never exceeds the
    per-device row footprint of its heaviest member's solo run.

    ``num_images_per_prompt`` multiplies batch rows, so bounding by job
    count alone would let e.g. 4 jobs x 8 images coalesce into a batch-32
    program — data_width times the per-device memory of any solo run, a
    likely OOM recovered only after a wasted large-batch compile. Greedy
    chunking keeps ceil(total_rows / dp) <= ceil(max_member_rows / dp)."""
    chunks: list[list] = []
    cur: list = []
    cur_rows = cur_max = 0
    # group members share COALESCE_KEYS (incl. height/width), so the
    # per-device row budget is uniform across the group
    per_device = single_chip_rows(group[0][3]) if group else 1
    for item in group:
        rows = job_rows(item[3])
        if cur and cur_rows + rows > rows_cap(max(cur_max, rows),
                                              data_width, per_device):
            chunks.append(cur)
            cur, cur_rows, cur_max = [], 0, 0
        cur.append(item)
        cur_rows += rows
        cur_max = max(cur_max, rows)
    if cur:
        chunks.append(cur)
    return chunks


def synchronous_do_work_batch(jobs: list[dict[str, Any]], slot,
                              registry: ModelRegistry) -> list[dict]:
    """Run a burst of jobs, coalescing compatible txt2img jobs into ONE
    batched program (workloads/diffusion.py::diffusion_coalesced_callback)
    — the dp-mesh efficiency path with no reference analog. Jobs that
    cannot coalesce (different static params, image inputs, non-diffusion
    workflows) run through the normal per-job path; a failed coalesced
    run falls back to per-job execution."""
    from chiaswarm_tpu.core.rng import draw_seed
    from chiaswarm_tpu.workloads.diffusion import (
        coalescable,
        diffusion_callback,
        diffusion_coalesced_callback,
    )

    if len(jobs) == 1:
        return [synchronous_do_work(jobs[0], slot, registry)]

    results: list[dict | None] = [None] * len(jobs)
    groups: dict[Any, list[tuple[int, Any, str, dict]]] = {}
    singles: list[tuple[int, Any, str, Any, dict]] = []
    # lane tickets: eligible jobs are submitted FIRST so their rows
    # splice into running lanes while the rest of the burst executes
    tickets: list[tuple[int, Any, str, dict, Any]] = []
    def _job_trace(i: int):
        return obs_trace.job_trace(jobs[i])

    for i, job in enumerate(jobs):
        log.info("processing job %s (burst of %d)", job.get("id"),
                 len(jobs))
        trace = _job_trace(i)
        if trace is not None:
            trace.gap("handover")
        with obs_trace.activate(trace):
            formatted, fatal = _format(job, registry)
            if formatted is None:
                results[i] = fatal
                continue
            job_id, content_type, callback, kwargs = formatted
            if callback is diffusion_callback:
                # lanes first (the default engine, ISSUE 7) — incl.
                # non-coalescable ControlNet jobs, which ride
                # bundle-keyed lanes the burst path has no analog for
                ticket = _stepper_submit(job_id, content_type, callback,
                                         kwargs, slot, registry)
                if ticket is not None:
                    tickets.append((i, job_id, content_type, kwargs,
                                    ticket))
                    continue
            if callback is diffusion_callback and coalescable(kwargs):
                groups.setdefault(_coalesce_key(kwargs), []).append(
                    (i, job_id, content_type, kwargs))
            else:
                singles.append((i, job_id, content_type, callback, kwargs))

    data_width = max(1, int(getattr(slot, "data_width", 1)))
    chunked = [chunk for whole in groups.values()
               for chunk in _row_chunks(whole, data_width)]
    for group in chunked:
        if len(group) == 1:
            i, job_id, content_type, kwargs = group[0]
            singles.append((i, job_id, content_type, diffusion_callback,
                            kwargs))
            continue
        from chiaswarm_tpu.workloads.diffusion import COALESCE_KEYS

        kwargs0 = group[0][3]
        shared = {k: kwargs0.get(k) for k in COALESCE_KEYS}
        per_job = []
        for i, job_id, content_type, kwargs in group:
            seed = kwargs.get("seed")  # 0 is a valid pinned seed
            per_job.append({
                "prompt": kwargs.get("prompt"),
                "negative_prompt": kwargs.get("negative_prompt"),
                "num_images_per_prompt":
                    kwargs.get("num_images_per_prompt", 1),
                "seed": draw_seed() if seed is None else int(seed),
                # per-job init/mask images (img2img/inpaint coalescing;
                # shapes/presence are uniform across the group by key)
                "image": kwargs.get("image"),
                "mask_image": kwargs.get("mask_image"),
                # solo-equivalence: an absent content_type must hit the
                # same default the solo callback uses (image/png), NOT
                # _format's error-payload jpeg default
                "content_type": kwargs.get("content_type", "image/png"),
            })
        ids = [job_id for _, job_id, _, _ in group]
        # one batched program serves the whole group: each member's
        # trace gets a "coalesced" span with the shared boundaries
        group_spans = []
        for i, _, _, _ in group:
            trace = _job_trace(i)
            if trace is not None:
                group_spans.append(
                    trace.tail().child("coalesced", jobs=len(group)))
        try:
            with _maybe_profile(f"coalesced-{ids[0]}"):
                outs = slot.call_multi(
                    diffusion_coalesced_callback,
                    model_name=kwargs0.get("model_name"),
                    seed=per_job[0]["seed"],
                    registry=registry, jobs=per_job, **shared)
            if len(outs) != len(group):  # never silently drop a job
                raise RuntimeError(
                    f"coalesced callback returned {len(outs)} results "
                    f"for {len(group)} jobs")
            log.info("coalesced %d jobs onto one program: %s",
                     len(group), ids)
            for (i, job_id, _, _), (artifacts, config) in zip(group, outs):
                results[i] = _result(job_id, artifacts, config)
        except Exception as exc:
            log.warning("coalesced run %s failed (%s); falling back to "
                        "per-job execution", ids, exc)
            for i, job_id, content_type, kwargs in group:
                singles.append((i, job_id, content_type,
                                diffusion_callback, kwargs))
        finally:
            for group_span in group_spans:
                group_span.end()

    # collect lane tickets after the burst groups dispatched: a failed
    # lane row falls back to the per-job path below (zero-loss)
    for i, job_id, content_type, kwargs, ticket in tickets:
        with obs_trace.activate(_job_trace(i)):
            result = _stepper_collect(job_id, content_type, slot, ticket,
                                      registry, kwargs)
        if result is not None:
            results[i] = result
        else:
            singles.append((i, job_id, content_type, diffusion_callback,
                            kwargs))

    for i, job_id, content_type, callback, kwargs in singles:
        with obs_trace.activate(_job_trace(i)), \
                checkpoint_scope(getattr(slot, "_checkpoint_spool", None),
                                 job_id):
            results[i] = _execute(job_id, content_type, callback, kwargs,
                                  slot)
    return [r for r in results if r is not None]
