"""Stable-diffusion workload callback: txt2img / img2img / inpaint.

Capability parity with swarm/diffusion/diffusion_func.py:14-124, redesigned
for the TPU runtime: instead of building a diffusers pipeline per job, the
job binds to a resident compile-cached DiffusionPipeline (node/registry.py)
and runs one jitted program. Memory-pressure heuristics (xformers/VAE
slicing/CPU offload, diffusion_func.py:76-94) have no TPU analog — the
equivalents are always on: Pallas flash attention, tiled VAE decode for
large outputs, bf16 weights.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from chiaswarm_tpu.node.output_processor import OutputProcessor
from chiaswarm_tpu.node.registry import ModelRegistry
from chiaswarm_tpu.node.resilience import phase_checkpoint
from chiaswarm_tpu.obs.trace import span
from chiaswarm_tpu.pipelines.diffusion import GenerateRequest


def diffusion_callback(slot, model_name: str, *, seed: int,
                       registry: ModelRegistry,
                       prompt: str = "",
                       negative_prompt: str = "",
                       num_inference_steps: int = 30,
                       guidance_scale: float = 7.5,
                       height: int | None = None,
                       width: int | None = None,
                       num_images_per_prompt: int = 1,
                       image: np.ndarray | None = None,
                       mask_image: np.ndarray | None = None,
                       strength: float = 0.75,
                       image_guidance_scale: float | None = None,
                       scheduler_type: str | None = None,
                       content_type: str = "image/png",
                       upscale: bool = False,
                       upscaler_model_name: str = (
                           "stabilityai/sd-x2-latent-upscaler"),
                       controlnet_model_name: str | None = None,
                       controlnet_scale: float = 1.0,
                       save_preprocessed_input: bool = False,
                       textual_inversion: str | None = None,
                       lora: str | None = None,
                       cross_attention_scale: float = 1.0,
                       reuse_schedule: Any = None,
                       outputs: tuple[str, ...] = ("primary",),
                       **_ignored: Any):
    # ``lora`` + ``cross_attention_scale`` are the reference's per-job LoRA
    # contract (swarm/diffusion/diffusion_func.py:20-22,58-68); here the
    # scaled deltas merge into a separately-cached param tree at load time
    pipe = registry.pipeline(model_name, textual_inversion=textual_inversion,
                             lora=lora, lora_scale=cross_attention_scale,
                             mesh=getattr(slot, "mesh", None))
    from chiaswarm_tpu.serving.residency import is_transient

    degraded = is_transient(pipe)  # load-per-job rung (serving/residency.py)
    fam = pipe.c.family
    if fam.kind != "sd":
        raise ValueError(
            f"model {model_name!r} is a {fam.kind} model, not a generation "
            f"pipeline; upscalers run via the server's 'upscale' parameter"
        )

    if image is not None:
        height, width = image.shape[:2]
    height = int(height or fam.default_size)
    width = int(width or fam.default_size)

    controlnet = None
    control_image = None
    if controlnet_model_name is not None:
        if fam.image_conditioned:
            raise ValueError(
                "instruct-pix2pix models do not support controlnet; the "
                "input image already conditions generation"
            )
        if mask_image is not None:
            raise ValueError(
                "controlnet jobs cannot also carry a mask_image; the input "
                "image is the conditioning image, not an inpainting source"
            )
        # the fetched input IS the (preprocessed) conditioning image — it
        # steers generation instead of seeding latents
        # (swarm/job_arguments.py:116-124)
        controlnet = registry.controlnet(controlnet_model_name, fam,
                                         mesh=getattr(slot, "mesh", None))
        control_image, image = image, None

    if image_guidance_scale is not None and not fam.image_conditioned:
        # image_guidance on a non-pix2pix checkpoint: honor the user's
        # intent through img2img strength (hive sends strength*5,
        # node/job_args.py remap)
        strength = min(1.0, max(0.05, float(image_guidance_scale) / 5.0))

    mask = None
    if mask_image is not None:
        m = np.asarray(mask_image, dtype=np.float32)
        if m.ndim == 3:
            m = m.mean(axis=-1)
        mask = m / 255.0 if m.max() > 1.0 else m

    req = GenerateRequest(
        prompt=prompt or "",
        negative_prompt=negative_prompt or "",
        steps=int(num_inference_steps),
        guidance_scale=float(guidance_scale),
        height=height,
        width=width,
        batch=max(1, int(num_images_per_prompt)),
        seed=seed,
        scheduler=scheduler_type,
        init_image=image,
        strength=float(strength),
        mask=mask,
        tiled_decode=max(height, width) > 1024,
        controlnet=controlnet,
        control_image=control_image,
        control_scale=float(controlnet_scale),
        image_guidance_scale=float(image_guidance_scale
                                   if image_guidance_scale is not None
                                   else 1.5),
        # DeepCache step-level reuse (ISSUE 12): engages only behind
        # CHIASWARM_DEEPCACHE; the pipeline normalizes and quality-gates
        reuse_schedule=(tuple(reuse_schedule)
                        if isinstance(reuse_schedule, (list, tuple))
                        else reuse_schedule),
    )
    # coarse phase checkpoints (ISSUE 6): the solo program has no step
    # boundary to snapshot at (encode/denoise/decode fuse into one
    # dispatch), so the spool records phase markers instead — "encoded"
    # once the model is bound and inputs are staged, "denoised" once the
    # expensive generation finished. A redelivered solo job restarts its
    # phase; the marker tells the fleet telemetry (and the operator) how
    # much chip time the death cost. Lane-riding jobs get real
    # step-boundary resume instead (serving/stepper.py).
    phase_checkpoint("encoded", model=str(model_name))
    t0 = time.perf_counter()
    images, config = pipe(req)
    elapsed = time.perf_counter() - t0
    phase_checkpoint("denoised", model=str(model_name),
                     generation_s=round(elapsed, 3))

    if upscale:
        # x2 latent upscale pass over the generated images, 20 steps at
        # guidance 0 (swarm/diffusion/upscale.py:6-32)
        upscaler = registry.pipeline(upscaler_model_name,
                                     mesh=getattr(slot, "mesh", None))
        images, up_config = upscaler(images, prompt=prompt or "", seed=seed)
        config.update(up_config)

    # swarmguard post-decode screen (ISSUE 10): a NaN-poisoned
    # trajectory must raise invalid_output here, never upload as a
    # "completed" black frame (serving/guard.py)
    from chiaswarm_tpu.serving.guard import screen_images

    with span("screen"):
        screen_images(images, context="solo decode")

    proc = OutputProcessor(content_type)
    with span("png"):  # encode, thumbnail, base64
        proc.add_images(images)
        if control_image is not None and save_preprocessed_input:
            # echo the preprocessed conditioning image back as an extra
            # artifact (swarm/diffusion/diffusion_func.py:36-39)
            proc.add_images(np.asarray(control_image, dtype=np.uint8),
                            key="preprocessed_input")
        artifacts = proc.get_results()

    if textual_inversion is not None:
        config["textual_inversion"] = textual_inversion
    if lora is not None:
        config["lora"] = lora
        config["cross_attention_scale"] = float(cross_attention_scale)
    from chiaswarm_tpu.workloads.safety import check_images

    with span("safety"):
        _, safety_fields = check_images(images, model_name)
    config.update(safety_fields)
    config.update({
        "images_per_sec": round(images.shape[0] / max(elapsed, 1e-9), 4),
        "generation_s": round(elapsed, 3),
        "slot": slot.descriptor() if hasattr(slot, "descriptor") else str(slot),
    })
    if degraded:
        # observable per job: this result paid a load (the model exceeds
        # the residency budget and serves load -> run -> release)
        config["residency"] = "per_job"
    return artifacts, config


# ---- cross-job coalescing (no reference analog) -----------------------
#
# A dp-sharded mesh slot replicates a batch=1 job on every data row —
# (dp-1)/dp of the slot does duplicate work. Compatible txt2img jobs
# (same model/size/steps/guidance/scheduler/adapters, no input images)
# instead ride ONE batched program: per-row prompts and per-row
# (seed, row) noise keys keep every job's images identical to its solo
# run (pipelines/diffusion.py sample_seed_rows). The executor groups
# queue bursts by COALESCE_KEYS (node/executor.py).

COALESCE_KEYS = ("num_inference_steps", "guidance_scale", "height",
                 "width", "scheduler_type", "textual_inversion", "lora",
                 "cross_attention_scale", "strength", "reuse_schedule")
# ControlNet conditions on the image (different program); pix2pix jobs
# carry image_guidance_scale (dual-CFG family, kept solo). Plain img2img
# and inpaint DO coalesce since r5: per-job init stacks + per-job
# VAE-encode seeds keep every job's images equal to its solo run
# (pipelines/diffusion.py GenerateRequest.init_groups).
_UNCOALESCABLE = ("controlnet_model_name", "image_guidance_scale")


def coalescable(kwargs: dict[str, Any]) -> bool:
    # upscale jobs run their x2 pass with the job's OWN prompt/seed —
    # batching them would condition every job on job 0's; keep them solo
    return (not kwargs.get("upscale")
            and all(kwargs.get(k) is None for k in _UNCOALESCABLE))


# ---- continuous step-level batching (serving/stepper.py) ---------------
#
# Lanes are the DEFAULT engine (ISSUE 7; CHIASWARM_STEPPER=0 opts out):
# eligible diffusion jobs skip the burst grouping entirely — each job's
# rows splice into the resident step loop of its lane at the next step
# boundary. Steps, guidance, img2img start indices, inpaint mask/known
# stacks and ControlNet hint embeddings all ride PER ROW, so txt2img,
# img2img and inpaint jobs with different parameters share one program
# (ControlNet rows ride bundle-keyed lanes), and a job arriving one poll
# late no longer waits behind a full solo program. The residue
# (pix2pix/upscale, explicit image_guidance remaps, low guidance,
# oversize, steps beyond the lattice) falls back to the burst/solo
# paths below.

def stepper_eligible(kwargs: dict[str, Any]) -> bool:
    """Can this (formatted) job ride a lane? Conservative pre-filter —
    serving.stepper.StepScheduler.submit_request is the authority and
    raises LaneReject for the residue (steps beyond the capacity
    lattice, rows wider than the lane cap, non-sd / pix2pix families)."""
    from chiaswarm_tpu.serving.stepper import stepper_enabled

    if not stepper_enabled():
        return False
    if kwargs.get("upscale"):
        return False  # the x2 pass chains a second pipeline — solo
    if kwargs.get("image_guidance_scale") is not None:
        return False  # pix2pix dual CFG / strength remap stays solo
    guidance = kwargs.get("guidance_scale")
    if guidance is not None and float(guidance) <= 1.0:
        # few-step kinds (ISSUE 12) are guidance-embedded: their native
        # CFG-free mode still rides lanes — the lane program's per-row
        # combine selects the pure conditional prediction
        from chiaswarm_tpu.schedulers.sampling import (
            FEWSTEP_KINDS,
            SAMPLERS,
        )

        if SAMPLERS.get(kwargs.get("scheduler_type") or "") not in \
                FEWSTEP_KINDS:
            return False  # solo compiles the no-CFG program
    if kwargs.get("mask_image") is not None \
            and kwargs.get("controlnet_model_name") is not None:
        return False  # invalid combination — solo raises the user error
    height = kwargs.get("height")
    width = kwargs.get("width")
    image = kwargs.get("image")
    if image is not None and getattr(image, "ndim", 0) >= 2:
        height, width = int(image.shape[0]), int(image.shape[1])
    if (height and int(height) > 1024) or (width and int(width) > 1024):
        return False  # tiled decode stays solo
    return True


@dataclasses.dataclass
class StepperTicket:
    """A submitted lane job: resolves through ``stepper_finish`` into the
    same (artifacts, config) contract the solo callback returns."""

    future: Any
    model_name: str
    family: str
    sampler_kind: str
    steps: int
    guidance: float
    req_hw: tuple[int, int]
    compiled_hw: tuple[int, int]
    rows: int
    seed: int
    content_type: str
    shared: dict[str, Any]
    slot: Any
    t0: float
    mode: str = "txt2img"
    denoise_steps: int = 0
    controlnet_name: str | None = None
    controlnet_scale: float = 1.0


def stepper_submit(slot, registry: ModelRegistry, kwargs: dict[str, Any],
                   seed: int, job_id: Any = None) -> StepperTicket:
    """Hand one formatted diffusion job (txt2img / img2img / inpaint /
    ControlNet, ISSUE 7) to the slot's step scheduler. Raises
    serving.stepper.LaneReject (or anything else) when the job must run
    through the ordinary path instead."""
    from chiaswarm_tpu.core.compile_cache import bucket_image_size
    from chiaswarm_tpu.schedulers import resolve
    from chiaswarm_tpu.serving.residency import is_transient
    from chiaswarm_tpu.serving.stepper import LaneReject, get_stepper

    model_name = kwargs.get("model_name")
    scale = kwargs.get("cross_attention_scale")
    pipe = registry.pipeline(
        model_name,
        textual_inversion=kwargs.get("textual_inversion"),
        lora=kwargs.get("lora"),
        lora_scale=1.0 if scale is None else float(scale),
        mesh=getattr(slot, "mesh", None))
    if is_transient(pipe):
        # degradation rung (serving/residency.py): a lane would hold the
        # over-budget params resident between jobs — run load-per-job
        # solo instead. The executor's lane_resident_ok pre-check makes
        # this a first-ever-load-only cost.
        raise LaneReject(
            f"model {model_name!r} degraded to load-per-job (residency)")
    fam = pipe.c.family
    image = kwargs.get("image")
    # ControlNet: the fetched input IS the conditioning image (exactly
    # the solo callback's remap); the bundle keys the lane
    controlnet = None
    control_image = None
    controlnet_name = kwargs.get("controlnet_model_name")
    if controlnet_name is not None:
        controlnet = registry.controlnet(controlnet_name, fam,
                                         mesh=getattr(slot, "mesh", None))
        control_image, image = image, None
    if image is not None:
        height, width = int(image.shape[0]), int(image.shape[1])
    else:
        height = int(kwargs.get("height") or fam.default_size)
        width = int(kwargs.get("width") or fam.default_size)
    steps = max(1, int(kwargs.get("num_inference_steps") or 30))
    guidance = kwargs.get("guidance_scale")
    guidance = 7.5 if guidance is None else float(guidance)
    rows = max(1, int(kwargs.get("num_images_per_prompt") or 1))
    # None-check, not `or`: strength=0.0 (near-identity img2img) and
    # controlnet_scale=0.0 (zero conditioning) are valid values the
    # solo callback honors — the lane path must quantize the same way
    strength = kwargs.get("strength")
    strength = 0.75 if strength is None else float(strength)
    cscale = kwargs.get("controlnet_scale")
    cscale = 1.0 if cscale is None else float(cscale)
    mask = None
    if kwargs.get("mask_image") is not None:
        # same normalization the solo callback applies before the
        # pipeline's latent-grid quantization
        m = np.asarray(kwargs["mask_image"], dtype=np.float32)
        if m.ndim == 3:
            m = m.mean(axis=-1)
        mask = m / 255.0 if m.max() > 1.0 else m
    # mode + executed-ladder suffix, mirroring the solo config contract
    # (the strength -> start-index quantization is an observable field)
    mode = ("inpaint" if mask is not None else
            "img2img" if image is not None else "txt2img")
    start_step = 0
    if mode == "img2img":
        from chiaswarm_tpu.pipelines.diffusion import img2img_start_index

        start_step = img2img_start_index(steps, strength)
    # redelivered jobs carry their dead worker's last lane checkpoint
    # (node/minihive.py): the scheduler splices the rows back in at the
    # recorded step instead of restarting at 0. A solo-path PHASE marker
    # (the dead worker ran this job outside a lane) carries no lane
    # state to splice — filter it silently, it is a routine redelivery,
    # not the tamper/corruption signal ResumeReject counts.
    resume = kwargs.get("resume")
    if not (isinstance(resume, dict) and resume.get("kind") == "lane"):
        resume = None
    future = get_stepper(slot).submit_request(
        pipe,
        prompt=str(kwargs.get("prompt") or ""),
        negative_prompt=str(kwargs.get("negative_prompt") or ""),
        steps=steps, guidance_scale=guidance,
        height=height, width=width, rows=rows, seed=int(seed),
        scheduler=kwargs.get("scheduler_type"),
        job_id=job_id,
        resume=resume,
        init_image=image, strength=strength, mask=mask,
        controlnet=controlnet, control_image=control_image,
        control_scale=cscale,
        reuse_schedule=kwargs.get("reuse_schedule"))
    sampler = resolve(kwargs.get("scheduler_type"),
                      prediction_type=fam.prediction_type)
    return StepperTicket(
        future=future, model_name=model_name, family=fam.name,
        sampler_kind=sampler.kind, steps=steps, guidance=guidance,
        req_hw=(height, width),
        compiled_hw=bucket_image_size(height, width),
        rows=rows, seed=int(seed),
        content_type=kwargs.get("content_type", "image/png"),
        shared={k: kwargs.get(k) for k in ("textual_inversion", "lora",
                                           "cross_attention_scale")},
        slot=slot, t0=time.perf_counter(),
        mode=mode, denoise_steps=steps - start_step,
        controlnet_name=controlnet_name,
        controlnet_scale=cscale)


def _lane_children(step_span, stamps: dict[str, float] | None) -> None:
    """The job's time inside the lane as children of its ``step`` span:
    ``lane.wait`` (submit -> admitted), ``lane.steps`` (admitted ->
    retire dispatch: the host's view of the job's steps) and
    ``lane.handoff`` (retire dispatch -> future resolved: the in-flight
    steps draining plus the VAE decode on the device)."""
    if not stamps:
        return
    marks = [stamps.get(k) for k in ("submitted", "admitted", "retired",
                                     "resolved")]
    for name, t0, t1 in zip(("lane.wait", "lane.steps", "lane.handoff"),
                            marks, marks[1:]):
        if t0 and t1:
            step_span.child_at(name, t0, t1)


def stepper_finish(ticket: StepperTicket):
    """Block on the lane rows, then postprocess exactly like the solo
    callback (un-bucket crop, safety, artifact encode)."""
    # the job's "step" span: admission wait + its rows' residency in the
    # lane's denoise loop (the lane-side timeline rides in as metadata)
    from chiaswarm_tpu.serving.stepper import LANE_STAMPS_KEY

    with span("step", steps=ticket.steps, rows=ticket.rows) as step_span:
        pending, lane_info = ticket.future.result()
        # the lane reports the job's time inside it as perf_counter
        # stamps it passed anyway (no extra synchronisation): three
        # children of "step" with explicit start and end
        stamps = lane_info.pop(LANE_STAMPS_KEY, None)
        step_span.meta.update(lane_info)
        _lane_children(step_span, stamps)
    # the lane decodes at the compiled bucket; un-bucket to the request
    pending.requested_hw = ticket.req_hw
    images = pending.wait()
    # swarmguard post-decode screen (ISSUE 10): rows whose poisoning
    # slipped past the checkpoint-boundary finite-check (e.g. a job
    # retiring between boundaries) are caught here — the envelope says
    # invalid_output, the garbage frame never uploads
    from chiaswarm_tpu.serving.guard import screen_images

    with span("screen"):
        screen_images(images, context="lane decode")
    elapsed = time.perf_counter() - ticket.t0

    proc = OutputProcessor(ticket.content_type)
    with span("png"):
        proc.add_images(images)
    config = {
        "model_name": ticket.model_name,
        "family": ticket.family,
        "scheduler": ticket.sampler_kind,
        "steps": ticket.steps,
        "denoise_steps": ticket.denoise_steps or ticket.steps,
        "guidance_scale": ticket.guidance,
        "size": list(ticket.req_hw),
        "compiled_size": list(ticket.compiled_hw),
        "batch": ticket.rows,
        "mode": ticket.mode,
        "seed": ticket.seed,
        "stepper": dict(lane_info),
    }
    if ticket.controlnet_name is not None:
        config["controlnet"] = ticket.controlnet_name
        config["controlnet_scale"] = ticket.controlnet_scale
    if ticket.shared.get("textual_inversion") is not None:
        config["textual_inversion"] = ticket.shared["textual_inversion"]
    if ticket.shared.get("lora") is not None:
        config["lora"] = ticket.shared["lora"]
        scale = ticket.shared.get("cross_attention_scale")
        config["cross_attention_scale"] = (1.0 if scale is None
                                           else float(scale))
    from chiaswarm_tpu.workloads.safety import check_images

    with span("safety"):
        _, safety_fields = check_images(images, ticket.model_name)
    config.update(safety_fields)
    config.update({
        "images_per_sec": round(images.shape[0] / max(elapsed, 1e-9), 4),
        "generation_s": round(elapsed, 3),
        "slot": (ticket.slot.descriptor()
                 if hasattr(ticket.slot, "descriptor")
                 else str(ticket.slot)),
    })
    with span("png"):  # encode, thumbnail, base64
        artifacts = proc.get_results()
    return artifacts, config


def diffusion_coalesced_callback(slot, model_name: str, *, seed: int,
                                 registry: ModelRegistry,
                                 jobs: list[dict[str, Any]],
                                 **shared: Any):
    """Run several compatible jobs as one batched program.

    ``jobs`` carries each job's per-row fields ({prompt, negative_prompt,
    num_images_per_prompt, seed, content_type}); ``shared`` carries the
    COALESCE_KEYS the executor verified equal. Returns a LIST of
    per-job (artifacts, config) in input order."""
    first = jobs[0]
    prompts: list[str] = []
    negs: list[str] = []
    seed_rows: list[tuple[int, int]] = []
    counts: list[int] = []
    for job in jobs:
        n = max(1, int(job.get("num_images_per_prompt", 1)))
        prompts += [str(job.get("prompt") or "")] * n
        negs += [str(job.get("negative_prompt") or "")] * n
        seed_rows += [(int(job["seed"]), r) for r in range(n)]
        counts.append(n)

    def opt(key: str, default):
        value = shared.get(key)  # present-but-None means "use default"
        return default if value is None else value

    pipe = registry.pipeline(
        model_name,
        textual_inversion=shared.get("textual_inversion"),
        lora=shared.get("lora"),
        lora_scale=opt("cross_attention_scale", 1.0),
        mesh=getattr(slot, "mesh", None))
    fam = pipe.c.family

    # img2img/inpaint: per-JOB init/mask stacks + per-job encode seeds
    # (the executor's coalesce key guarantees uniform image shapes and
    # mask presence across the group)
    has_img = first.get("image") is not None
    init_stack = mask_stack = init_groups = None
    if has_img:
        if fam.image_conditioned:
            # pix2pix-family jobs are excluded upstream; a miss here must
            # fall back to the per-job path, not mis-serve dual CFG
            raise ValueError("image-conditioned (pix2pix) jobs do not "
                             "coalesce")
        init_stack = np.stack([np.asarray(j["image"]) for j in jobs])
        init_groups = tuple((int(j["seed"]), n)
                            for j, n in zip(jobs, counts))
        if first.get("mask_image") is not None:
            masks = []
            for job in jobs:
                m = np.asarray(job["mask_image"], dtype=np.float32)
                if m.ndim == 3:
                    m = m.mean(axis=-1)
                masks.append(m / 255.0 if m.max() > 1.0 else m)
            mask_stack = np.stack(masks)
        height, width = init_stack.shape[1:3]
    else:
        height = int(opt("height", fam.default_size))
        width = int(opt("width", fam.default_size))

    req = GenerateRequest(
        prompt=tuple(prompts),
        negative_prompt=tuple(negs),
        steps=int(opt("num_inference_steps", 30)),
        guidance_scale=float(opt("guidance_scale", 7.5)),
        height=int(height),
        width=int(width),
        batch=len(prompts),
        seed=int(first["seed"]),
        sample_seed_rows=tuple(seed_rows),
        scheduler=shared.get("scheduler_type"),
        init_image=init_stack,
        init_groups=init_groups,
        strength=float(opt("strength", 0.75)),
        mask=mask_stack,
        tiled_decode=max(int(height), int(width)) > 1024,
        # part of the coalesce key, so every member shares one schedule
        reuse_schedule=(tuple(shared["reuse_schedule"])
                        if isinstance(shared.get("reuse_schedule"),
                                      (list, tuple))
                        else shared.get("reuse_schedule")),
    )
    t0 = time.perf_counter()
    images, base_config = pipe(req)
    elapsed = time.perf_counter() - t0

    # swarmguard post-decode screen (ISSUE 10): the invariant — no
    # poisoned frame ever uploads — must hold on the coalesced path
    # too. Raising fails the WHOLE batched run, and the executor's
    # fallback re-runs every member per-job (zero-loss): the poisoned
    # job then gets its precise invalid_output envelope from the solo
    # screen while healthy peers complete.
    from chiaswarm_tpu.serving.guard import screen_images

    # (no job trace is active here — one program serves the group — so
    # screen / png / safety below name the profiler's clock only)
    with span("screen"):
        screen_images(images, context="coalesced decode")

    from chiaswarm_tpu.workloads.safety import check_images

    results = []
    offset = 0
    for job, n in zip(jobs, counts):
        imgs = images[offset:offset + n]
        offset += n
        proc = OutputProcessor(job.get("content_type", "image/png"))
        with span("png"):
            proc.add_images(imgs)
        config = dict(base_config)
        config["seed"] = int(job["seed"])
        config["batch"] = n
        # same adapter metadata the solo path records
        if shared.get("textual_inversion") is not None:
            config["textual_inversion"] = shared["textual_inversion"]
        if shared.get("lora") is not None:
            config["lora"] = shared["lora"]
            config["cross_attention_scale"] = float(
                opt("cross_attention_scale", 1.0))
        with span("safety"):
            _, safety_fields = check_images(imgs, model_name)
        config.update(safety_fields)
        config.update({
            "coalesced": len(jobs),
            # per-job number keeps solo semantics (this job's images over
            # this job's wall time); the whole program's throughput is
            # reported separately so aggregators do not k-fold overcount
            "images_per_sec": round(n / max(elapsed, 1e-9), 4),
            "batch_images_per_sec": round(
                images.shape[0] / max(elapsed, 1e-9), 4),
            "generation_s": round(elapsed, 3),
            "slot": (slot.descriptor() if hasattr(slot, "descriptor")
                     else str(slot)),
        })
        with span("png"):
            artifacts = proc.get_results()
        results.append((artifacts, config))
    return results
