"""Benchmark: the five BASELINE.json configs, measured end to end.

Headline is the north-star config — SDXL 1024px txt2img, 30 steps, CFG —
through the jitted pipeline (text encode -> scan denoise -> VAE decode) on
the default backend. The other four configs (SD1.5-512/20-DDIM, SD2.1
img2img + inpaint, ControlNet+SDXL, txt2vid) run the same way. Random
host-materialized bf16 weights (identical FLOPs/memory traffic to
converted checkpoints). On non-TPU hosts the script falls back to the tiny
hermetic family so it stays runnable anywhere.

Prints ONE JSON line: the headline metric fields at the top level
({"metric", "value", "unit", "vs_baseline", ...}, same schema as round 1)
plus a "configs" object with one entry per BASELINE.json config.
`vs_baseline` is vs the driver-set target of 4 images/sec/chip
(BASELINE.json "north_star"; the reference itself publishes no numbers —
BASELINE.md).

Throughput is measured steady-state: jobs are submitted back-to-back via
``DiffusionPipeline.submit`` so job N's device->host uint8 transfer
overlaps job N+1's denoise (serving does the same; the reference's torch
pipelines block per call).

Env knobs: CHIASWARM_BENCH_CONFIGS (comma list or "all" / "headline"),
CHIASWARM_BENCH_ITERS, CHIASWARM_BENCH_ATTN, and for the headline
CHIASWARM_BENCH_FAMILY/SIZE/STEPS/BATCH.
"""

from __future__ import annotations

import json
import os
import time


def _percentile50(times: list[float]) -> float:
    return sorted(times)[len(times) // 2]


def _step_seconds_snapshot() -> dict | None:
    """Percentiles of the process-cumulative lane step-seconds
    histogram (swarmlens, ISSUE 11) — None before any lane stepped."""
    from chiaswarm_tpu.obs.metrics import REGISTRY

    hist = REGISTRY.get("chiaswarm_stepper_step_seconds")
    if hist is None or not hist.count():
        return None
    pct = hist.percentiles((0.5, 0.9, 0.99))
    if pct is None:
        return None
    return dict({k: round(v, 6) for k, v in pct.items()},
                count=hist.count())


def _bench_diffusion(pipe, *, size: int, steps: int, batch: int, iters: int,
                     scheduler: str | None = None, init_image=None,
                     mask=None, controlnet=None, control_image=None,
                     pipelined: bool = False, roofline: bool = True,
                     guidance: float = 7.5, reuse_schedule=None) -> dict:
    """Warm once, then measure. ``pipelined=True`` additionally measures
    steady-state throughput with submit/wait overlap.

    ``roofline=True`` (swarmlens, ISSUE 11) AOT-captures the generate
    program during the warm call and stamps its static roofline model
    (modeled FLOPs/bytes, the compute-vs-memory bound, attainment vs
    the measured p50) into the result — the per-config *where does the
    chip time go* signal the r06+ BENCH trajectory tracks next to
    img/s. Peaks are the TPU defaults, so on CPU hosts the attainment
    percentage is notional while the modeled-work numbers stay exact."""
    import numpy as np

    import chiaswarm_tpu.pipelines.diffusion as diffusion_mod
    from chiaswarm_tpu.obs import hlocost
    from chiaswarm_tpu.pipelines.diffusion import GenerateRequest

    def req(seed: int) -> GenerateRequest:
        return GenerateRequest(
            prompt="a photograph of an astronaut riding a horse",
            negative_prompt="blurry", steps=steps, guidance_scale=guidance,
            height=size, width=size, batch=batch, seed=seed,
            scheduler=scheduler, init_image=init_image, strength=0.75,
            mask=mask, controlnet=controlnet, control_image=control_image,
            reuse_schedule=reuse_schedule,
        )

    capture = hlocost.ProgramCapture()
    if roofline:
        # the warm call is where the cold build happens — capture it;
        # later calls ride the same AOT executables, so measurement
        # semantics are unchanged
        with capture.patching(diffusion_mod):
            imgs, config = pipe(req(0))
    else:
        imgs, config = pipe(req(0))
    assert imgs.shape[0] == batch

    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        pipe(req(i + 1))
        times.append(time.perf_counter() - t0)
    p50 = _percentile50(times)
    out = {
        "p50_latency_s": round(p50, 3),
        "images_per_sec": round(batch / p50, 4),
        # step-collapse accounting (ISSUE 12): FULL UNet evals each
        # image pays — the cost term the >=4x reduction gate reads
        "unet_evals_per_image": config.get("unet_evals",
                                           config.get("denoise_steps",
                                                      steps)),
    }
    if roofline:
        hlo = capture.largest_hlo()
        if hlo:
            # fold the while body by the steps the ladder actually ran
            # (img2img strength truncates the ladder — the observable
            # denoise_steps contract)
            out["roofline"] = hlocost.static_program_report(
                hlo, steps=int(config.get("denoise_steps", steps)),
                achieved_s=p50)
            # swarmproof (ISSUE 15): the same captured program's HLO
            # contract facts — collective counts (any collective in a
            # single-chip config is a compiler surprise; an all-reduce
            # in a ring config is the runtime face of R11), matmul
            # dtype census, and what survived of buffer donation —
            # stamped per config so drift across rounds is a BENCH
            # diff, not a TPU postmortem
            from chiaswarm_tpu.analysis import hlocheck

            out["hlo_contract"] = hlocheck.census(hlo)

    if pipelined:
        # steady-state: keep one job in flight while fetching the last
        n = max(4, iters)
        t0 = time.perf_counter()
        pending = pipe.submit(req(100))[0]
        for i in range(1, n):
            nxt = pipe.submit(req(100 + i))[0]
            pending.wait()
            pending = nxt
        pending.wait()
        total = time.perf_counter() - t0
        out["images_per_sec_pipelined"] = round(n * batch / total, 4)
    return out


def _bench_mixed_arrival(*, on_tpu: bool, attn: str) -> dict:
    """Continuous step-level admission (serving/stepper.py) vs burst-only
    coalescing under STAGGERED mixed-steps arrivals — the traffic shape
    the burst path cannot batch at all: jobs arrive in different polls
    and with different step counts, so `synchronous_do_work_batch` runs
    every one as a solo program while the step scheduler splices each
    into the resident lane at the next step boundary.

    Runs on a dp-sharded mesh slot when enough devices exist (the virtual
    8-device CPU mesh in CI): a solo batch-1 program replicates over the
    data axis, wasting (dp-1)/dp of the slot — exactly what lane
    occupancy recovers. Lanes run UNSHARDED here, matching serving: on
    the pinned jax build the row-sharded step program has a known
    numerics divergence (ROADMAP item 2, the GSPMD divergence family),
    so the bench must not publish throughput from a program the serving
    path refuses to run. Re-enable CHIASWARM_STEPPER_SHARD_ROWS in this
    config when ROADMAP item 2 lands."""
    import os
    import time

    import jax

    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.pipelines.diffusion import GenerateRequest
    from chiaswarm_tpu.serving.stepper import StepScheduler

    fam = "sd15" if on_tpu else "tiny"
    size = 512 if on_tpu else 64
    steps_mix = [20, 25, 30] if on_tpu else [6, 8, 10]
    n_dev = len(jax.devices())
    if n_dev >= 8:
        mesh = build_mesh(MeshSpec({"data": 4, "model": 2}))
    elif n_dev >= 2:
        mesh = build_mesh(MeshSpec({"data": n_dev}))
    else:
        mesh = None
    dp = 1 if mesh is None else dict(
        zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)

    saved = {k: os.environ.get(k) for k in
             ("CHIASWARM_STEPPER_LANE_WIDTH", "CHIASWARM_STEPPER_SHARD_ROWS")}
    os.environ["CHIASWARM_STEPPER_LANE_WIDTH"] = str(max(2, dp))
    # ROADMAP item 2: sharded lanes diverge numerically on the pinned
    # build — serving runs lanes unsharded, and so does the bench
    os.environ["CHIASWARM_STEPPER_SHARD_ROWS"] = "0"
    try:
        registry = ModelRegistry(
            catalog=[{"name": fam, "family": fam, "parameters": {}}],
            allow_random=True, attn_impl=attn)
        pipe = registry.pipeline(fam, mesh=mesh)
        jobs = [(f"job {i}", steps_mix[i % len(steps_mix)], 300 + i)
                for i in range(8)]

        def req(prompt, steps, seed):
            return GenerateRequest(prompt=prompt, steps=steps,
                                   guidance_scale=7.5, height=size,
                                   width=size, seed=seed)

        # warm every solo program + the lane executables
        for steps in sorted(set(s for _, s, _ in jobs)):
            pipe(req("warm", steps, 0))
        sched = StepScheduler()
        sched.submit_request(pipe, prompt="warm", steps=max(steps_mix),
                             guidance_scale=7.5, height=size, width=size,
                             rows=1, seed=0).result(timeout=600)[0].wait()
        s0 = dict(sched.stats())
        t0 = time.perf_counter()
        sched.submit_request(pipe, prompt="warm2", steps=max(steps_mix),
                             guidance_scale=7.5, height=size, width=size,
                             rows=1, seed=1).result(timeout=600)[0].wait()
        step_t = (time.perf_counter() - t0) / max(
            1, sched.stats()["steps_executed"] - s0["steps_executed"])
        # arrivals one lane-step apart: several polls' worth of traffic
        # lands while any one job is still denoising — the regime burst
        # coalescing serves as N solo programs
        stagger = step_t

        def arrivals(run_one):
            t_start = time.perf_counter()
            handles = []
            for i, job in enumerate(jobs):
                target = t_start + i * stagger
                now = time.perf_counter()
                if now < target:
                    time.sleep(target - now)
                handles.append(run_one(job))
            return t_start, handles

        # burst-only reality for this arrival stream: one solo program
        # per job (mixed steps never share a _coalesce_key), submit/wait
        # pipelined like the serving slots
        t_start, handles = arrivals(
            lambda job: pipe.submit(req(*job))[0])
        for pending in handles:
            pending.wait()
        burst_total = time.perf_counter() - t_start

        before = dict(sched.stats())
        t_start, handles = arrivals(
            lambda job: sched.submit_request(
                pipe, prompt=job[0], steps=job[1], guidance_scale=7.5,
                height=size, width=size, rows=1, seed=job[2]))
        for fut in handles:
            fut.result(timeout=600)[0].wait()
        cont_total = time.perf_counter() - t_start
        after = dict(sched.stats())
        sched.shutdown()

        active = after["row_steps_active"] - before["row_steps_active"]
        padded = (after.get("row_steps_padded", 0)
                  - before.get("row_steps_padded", 0))
        denom = max(1, active + padded)
        return {
            "jobs": len(jobs),
            "steps_mix": steps_mix,
            "stagger_s": round(stagger, 4),
            # swarmlens (ISSUE 11): the live lane step-latency
            # distribution — the signal the measured hang budget and
            # deadline tables derive from
            "step_seconds": _step_seconds_snapshot(),
            "images_per_sec_continuous": round(len(jobs) / cont_total, 4),
            "images_per_sec_burst_only": round(len(jobs) / burst_total, 4),
            "speedup": round(burst_total / cont_total, 4),
            "lane_occupancy": round(active / denom, 4),
            "padding_waste": round(padded / denom, 4),
            "rows_admitted_midflight": (
                after.get("rows_admitted_midflight", 0)
                - before.get("rows_admitted_midflight", 0)),
            "lane_width": max(2, dp),
            "mesh_data_axis": dp,
            # lanes run unsharded until the ROADMAP-item-2 numerics
            # divergence is debugged (the key stays for r-trajectory
            # continuity in BENCH json diffs)
            "sharded_rows": False,
        }
    finally:
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value


def _bench_mixed_workloads(*, on_tpu: bool, attn: str) -> dict:
    """Adaptive-width lanes under a staggered txt2img + img2img + inpaint
    arrival stream (ISSUE 7): the workload mix real hive traffic shows,
    where the burst path cannot coalesce ACROSS workloads at all and the
    static-width lane pays the padding for whichever regime it guessed.

    Two runs over the identical arrival schedule: per-job solo programs
    (submit/wait pipelined — the pre-ISSUE-7 reality for img2img and
    inpaint, which were lane-ineligible) vs adaptive-width lanes
    (CHIASWARM_STEPPER_LANE_WIDTH unset, so the occupancy/arrival-rate
    controller sets capacity). Reported per workload: p50 latency both
    ways plus the lane occupancy, padding-waste, resize-count, and
    per-workload admission counters from the scheduler stats — the r06
    BENCH json trajectory for the adaptive-width win."""
    import os
    import time

    import jax
    import numpy as np

    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.pipelines.diffusion import GenerateRequest
    from chiaswarm_tpu.serving.stepper import StepScheduler

    fam = "sd15" if on_tpu else "tiny"
    size = 512 if on_tpu else 64
    steps_mix = [20, 25, 30] if on_tpu else [6, 8, 10]
    # same slot shape as _bench_mixed_arrival: a dp-sharded mesh when
    # devices allow (the virtual 8-device CPU mesh in CI) — a solo
    # batch-1 program replicates over the data axis, wasting (dp-1)/dp
    # of the slot, which is exactly the capacity lanes pack rows into
    n_dev = len(jax.devices())
    if n_dev >= 8:
        mesh = build_mesh(MeshSpec({"data": 4, "model": 2}))
    elif n_dev >= 2:
        mesh = build_mesh(MeshSpec({"data": n_dev}))
    else:
        mesh = None
    dp = 1 if mesh is None else dict(
        zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)

    rng = np.random.default_rng(7)
    init = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
    half_mask = np.zeros((size, size), np.float32)
    half_mask[size // 2:] = 1.0

    # the arrival stream: workloads interleaved so no two consecutive
    # jobs share a solo program, steps mixed so no two share a burst key
    kinds = ["txt2img", "img2img", "txt2img", "inpaint",
             "img2img", "txt2img", "inpaint", "txt2img",
             "img2img", "inpaint", "txt2img", "img2img"]
    jobs = [(kind, steps_mix[i % len(steps_mix)], 700 + i)
            for i, kind in enumerate(kinds)]

    saved = {k: os.environ.get(k) for k in
             ("CHIASWARM_STEPPER_LANE_WIDTH", "CHIASWARM_STEPPER_SHARD_ROWS",
              "CHIASWARM_STEPPER_ADAPTIVE", "CHIASWARM_STEPPER_MAX_WIDTH")}
    # adaptive width on (the ISSUE-7 default): no pinned width, bounds
    # left to the controller; lanes unsharded per ROADMAP item 2
    os.environ.pop("CHIASWARM_STEPPER_LANE_WIDTH", None)
    os.environ.pop("CHIASWARM_STEPPER_ADAPTIVE", None)
    os.environ["CHIASWARM_STEPPER_SHARD_ROWS"] = "0"
    os.environ["CHIASWARM_STEPPER_MAX_WIDTH"] = "8"
    try:
        registry = ModelRegistry(
            catalog=[{"name": fam, "family": fam, "parameters": {}}],
            allow_random=True, attn_impl=attn)
        pipe = registry.pipeline(fam, mesh=mesh)

        def req(kind: str, steps: int, seed: int) -> GenerateRequest:
            return GenerateRequest(
                prompt=f"{kind} {seed}", steps=steps, guidance_scale=7.5,
                height=size, width=size, seed=seed,
                init_image=init if kind != "txt2img" else None,
                strength=0.6,
                mask=half_mask if kind == "inpaint" else None)

        def lane_submit(sched, kind, steps, seed):
            return sched.submit_request(
                pipe, prompt=f"{kind} {seed}", steps=steps,
                guidance_scale=7.5, height=size, width=size, rows=1,
                seed=seed,
                init_image=init if kind != "txt2img" else None,
                strength=0.6,
                mask=half_mask if kind == "inpaint" else None)

        # warm every solo program and lane executable the stream needs
        for kind in ("txt2img", "img2img", "inpaint"):
            for steps in sorted(set(s for _, s, _ in jobs)):
                pipe(req(kind, steps, 0))
        sched = StepScheduler()
        lane_submit(sched, "inpaint", max(steps_mix), 1).result(
            timeout=600)[0].wait()
        s0 = dict(sched.stats())
        t0 = time.perf_counter()
        lane_submit(sched, "img2img", max(steps_mix), 2).result(
            timeout=600)[0].wait()
        step_t = (time.perf_counter() - t0) / max(
            1, sched.stats()["steps_executed"] - s0["steps_executed"])
        stagger = step_t

        def arrivals(run_one):
            t_start = time.perf_counter()
            handles = []
            for i, job in enumerate(jobs):
                target = t_start + i * stagger
                now = time.perf_counter()
                if now < target:
                    time.sleep(target - now)
                handles.append((job[0], time.perf_counter(), run_one(job)))
            return t_start, handles

        def p50_by_kind(samples: list[tuple[str, float]]) -> dict:
            out = {}
            for kind in ("txt2img", "img2img", "inpaint"):
                lat = sorted(t for k, t in samples if k == kind)
                if lat:
                    out[kind] = round(lat[len(lat) // 2], 4)
            return out

        # per-job reality for this stream: every job its own solo
        # program (img2img/inpaint had NO batched path before ISSUE 7)
        t_start, handles = arrivals(
            lambda job: pipe.submit(req(*job))[0])
        solo_lat = []
        for kind, t_sub, pending in handles:
            pending.wait()
            solo_lat.append((kind, time.perf_counter() - t_sub))
        solo_total = time.perf_counter() - t_start

        before = dict(sched.stats())
        t_start, handles = arrivals(
            lambda job: lane_submit(sched, *job))
        lane_lat = []
        for kind, t_sub, fut in handles:
            fut.result(timeout=600)[0].wait()
            lane_lat.append((kind, time.perf_counter() - t_sub))
        lane_total = time.perf_counter() - t_start
        after = dict(sched.stats())
        sched.shutdown()

        active = after["row_steps_active"] - before["row_steps_active"]
        padded = (after.get("row_steps_padded", 0)
                  - before.get("row_steps_padded", 0))
        denom = max(1, active + padded)
        admitted = {
            kind: (after.get(f"rows_admitted_{kind}", 0)
                   - before.get(f"rows_admitted_{kind}", 0))
            for kind in ("txt2img", "img2img", "inpaint")}
        return {
            "jobs": len(jobs),
            "step_seconds": _step_seconds_snapshot(),
            "workload_mix": {k: kinds.count(k) for k in
                             ("txt2img", "img2img", "inpaint")},
            "steps_mix": steps_mix,
            "stagger_s": round(stagger, 4),
            "images_per_sec_lanes": round(len(jobs) / lane_total, 4),
            "images_per_sec_per_job": round(len(jobs) / solo_total, 4),
            "speedup": round(solo_total / lane_total, 4),
            "p50_latency_s_lanes": p50_by_kind(lane_lat),
            "p50_latency_s_per_job": p50_by_kind(solo_lat),
            "lane_occupancy": round(active / denom, 4),
            "padding_waste": round(padded / denom, 4),
            "lane_resizes": (after.get("lane_resizes", 0)
                             - before.get("lane_resizes", 0)),
            "rows_admitted_by_workload": admitted,
            "rows_admitted_midflight": (
                after.get("rows_admitted_midflight", 0)
                - before.get("rows_admitted_midflight", 0)),
            "adaptive_width": True,
            "mesh_data_axis": dp,
            "sharded_rows": False,
        }
    finally:
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value


def _bench_step_collapse(*, on_tpu: bool, attn: str, iters: int) -> dict:
    """ISSUE 12 (swarmturbo): the step-collapse configs — the arc that
    attacks the 15x headline gap where the per-image math itself
    shrinks, not the scheduling around it.

    Two configs, both quality-accounted against the SAME-seed full-step
    reference (the int8 pattern: the trick ships gated, not trusted):

    - ``sdxl_txt2img_1024_4step``: the lcm-kind few-step sampler at 4
      steps, guidance-embedded (CFG-free at guidance 1.0) — collapses
      steps 30 -> 4 (a >=4x per-image UNet-eval reduction by
      construction, stamped and asserted from the measured config).
    - ``sdxl_txt2img_1024_deepcache``: the 30-step ladder with a
      DeepCache ``every:2`` refresh cadence — half the deep-UNet passes
      replay the cached deep features; PSNR/SSIM vs the reuse-off
      reference is the gate (>= 30 dB / >= 0.9).

    On CPU hosts the tiny hermetic family stands in (exactly like the
    headline config) — eval counts and the quality gate are real, the
    img/s notional."""
    import jax

    from chiaswarm_tpu.obs.quality import quality_report
    from chiaswarm_tpu.pipelines.components import Components
    from chiaswarm_tpu.pipelines.diffusion import (
        DiffusionPipeline,
        GenerateRequest,
    )

    fam = "sdxl" if on_tpu else "tiny"
    size = 1024 if on_tpu else 64
    base_steps = 30  # the headline ladder — the cost term being collapsed
    few_steps = 4
    c = Components.random(fam, seed=0)
    c.params = jax.device_put(c.params, jax.devices()[0])
    pipe = DiffusionPipeline(c, attn_impl=attn)

    prompt = "a photograph of an astronaut riding a horse"
    seed = 123

    # full-step reference: the quality-gate anchor and the eval baseline
    ref_imgs, ref_cfg = pipe(GenerateRequest(
        prompt=prompt, steps=base_steps, guidance_scale=7.5,
        height=size, width=size, seed=seed))
    baseline_evals = int(ref_cfg["unet_evals"])

    out: dict[str, dict] = {}

    # ---- few-step family (lcm kind, CFG-free) ----
    fewstep = _bench_diffusion(
        pipe, size=size, steps=few_steps, batch=1, iters=iters,
        scheduler="LCMScheduler", guidance=1.0, pipelined=True)
    few_imgs, few_cfg = pipe(GenerateRequest(
        prompt=prompt, steps=few_steps, guidance_scale=1.0,
        height=size, width=size, seed=seed, scheduler="LCMScheduler"))
    fewstep.update({
        "steps": few_steps,
        "scheduler": "lcm",
        "guidance_scale": 1.0,
        "baseline_unet_evals": baseline_evals,
        "unet_evals_reduction": round(
            baseline_evals / max(1, int(few_cfg["unet_evals"])), 2),
        # informational only: a distilled few-step checkpoint changes
        # the trajectory CLASS, so similarity to the 30-step reference
        # is reported, not gated (random weights make it meaningless
        # anyway; the lcm gate is lane-vs-solo exactness, test_fewstep)
        "quality_vs_reference": dict(
            quality_report(few_imgs, ref_imgs), gated=False),
    })
    out["sdxl_txt2img_1024_4step"] = fewstep

    # ---- DeepCache feature reuse (every:2 cadence) ----
    saved = os.environ.get("CHIASWARM_DEEPCACHE")
    os.environ["CHIASWARM_DEEPCACHE"] = "1"
    try:
        deepcache = _bench_diffusion(
            pipe, size=size, steps=base_steps, batch=1, iters=iters,
            reuse_schedule="every:2", pipelined=True)
        dc_imgs, dc_cfg = pipe(GenerateRequest(
            prompt=prompt, steps=base_steps, guidance_scale=7.5,
            height=size, width=size, seed=seed,
            reuse_schedule="every:2"))
    finally:
        if saved is None:
            os.environ.pop("CHIASWARM_DEEPCACHE", None)
        else:
            os.environ["CHIASWARM_DEEPCACHE"] = saved
    deepcache.update({
        "steps": base_steps,
        "reuse_schedule": "every:2",
        "steps_skipped": int(dc_cfg["steps_skipped"]),
        "baseline_unet_evals": baseline_evals,
        "unet_evals_reduction": round(
            baseline_evals / max(1, int(dc_cfg["unet_evals"])), 2),
        # THE gate (same seed, same sampler, reuse on vs off): ships
        # only while the cached-feature output stays faithful
        "quality_vs_reference": dict(
            quality_report(dc_imgs, ref_imgs), gated=True),
    })
    out["sdxl_txt2img_1024_deepcache"] = deepcache
    del pipe, c
    return out


def _bench_model_churn(*, on_tpu: bool, attn: str) -> dict:
    """ISSUE 8: model-swap latency + resident-model count under a budget
    that cannot hold the catalog — the residency ledger's headline
    numbers (evict-then-load donation, measured footprints), stamped
    into BENCH json. CPU hosts churn the tiny family; TPU churns
    sd15-class checkpoints (random weights — load+convert cost is real,
    weight content does not change it)."""
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.obs.metrics import Registry as ObsRegistry
    from chiaswarm_tpu.serving.residency import ResidencyManager

    family = "sd15" if on_tpu else "tiny"
    models = [f"bench/churn-{tag}" for tag in "abc"]

    def build(budget_bytes: int | None) -> tuple:
        manager = ResidencyManager(
            budget_bytes=budget_bytes or (1 << 40),
            hard_limit_bytes=(budget_bytes or (1 << 40)) * 8,
            metrics_registry=ObsRegistry(), persist_path=None)
        registry = ModelRegistry(
            catalog=[{"name": name, "family": family} for name in models],
            allow_random=True, residency=manager, attn_impl=attn)
        return manager, registry

    # probe one load for the measured footprint the budget is
    # denominated in (exactly what production learns on load one)
    probe_manager, probe_registry = build(None)
    probe_registry.pipeline(models[0])
    footprint = probe_manager.measured_footprints()[models[0]]

    budget = int(footprint * 1.5)  # one resident at a time: every
    manager, registry = build(budget)  # model switch is a swap
    manager.reset_peak()
    swap_times: list[float] = []
    hit_times: list[float] = []
    for round_i in range(2):
        for name in models:
            before = manager.misses
            t0 = time.perf_counter()
            pipe = registry.pipeline(name)
            # touch the pipeline so lazy placement settles into the time
            del pipe
            elapsed = time.perf_counter() - t0
            (swap_times if manager.misses > before
             else hit_times).append(elapsed)
    snap = manager.snapshot()
    largest = max(manager.measured_footprints().values())
    return {
        "family": family,
        "models": len(models),
        "budget_bytes": budget,
        "footprint_bytes": footprint,
        "swap_p50_s": round(_percentile50(swap_times), 4),
        "swaps": len(swap_times),
        "hit_p50_s": (round(_percentile50(hit_times), 6)
                      if hit_times else 0.0),
        "evictions": snap["evictions"],
        "resident_models": len(snap["resident_models"]),
        "resident_bytes": snap["resident_bytes"],
        "peak_bytes": snap["peak_bytes"],
        # THE no-double-buffer invariant, stamped per run
        "peak_within_budget_plus_one": bool(
            snap["peak_bytes"] <= budget + largest),
        "weights_format": os.environ.get("CHIASWARM_WEIGHTS", "bf16"),
    }


def _bench_load_harness(*, on_tpu: bool, attn: str) -> dict:
    """ISSUE 9: the swarmload capacity model + tuning sweeps, stamped
    into BENCH json. One compact seeded diurnal 10x-overload run with a
    mid-run worker kill through the mini-hive (synthetic overload-
    controlled workers — this config measures the CONTROL plane:
    shed/backpressure/brownout behavior and jobs/s/chip at fleet scale,
    not pipeline FLOPs, so it runs identically on CPU and TPU hosts),
    plus the pure-host controller sweeps whose winners are the shipped
    LaneWidthController gains and residency prefetch-ranking window
    (tests/test_loadgen.py pins defaults == winner)."""
    import asyncio

    from chiaswarm_tpu.node import loadgen

    seed = "swarmload"  # FIXED: BENCH r-trajectories must diff runs,
    # not seeds (the nightly chaos soak explores fresh seeds instead)
    schedule = loadgen.build_scenario(seed=seed, n_users=1000,
                                      duration_s=2.5, rate_jobs_s=120)
    report = asyncio.run(loadgen.run_load(
        schedule, n_workers=3, seed=seed, lease_s=3.0,
        max_jobs_per_poll=4, kill=loadgen.KillPlan(after_frac=0.5),
        settle_timeout_s=180))
    workers = report["workers"]
    return {
        "seed": seed,
        "capacity_model": report["capacity"],
        "offered": report["offered"],
        "outcomes": report["outcomes"],
        "zero_loss": report["reconciliation"]["zero_loss"],
        "admitted_p99_within_deadline":
            report["admitted_deadline"]["p99_within_deadline"],
        "latency_s": report["latency_s"]["end_to_end"],
        "jobs_shed": sum(w["jobs_shed"] for w in workers.values()),
        "polls_backpressured": sum(w["polls_backpressured"]
                                   for w in workers.values()),
        "kill": report["kill"],
        # measured per-family deadline suggestions (ISSUE 10 satellite)
        "suggested_deadlines": report["suggested_deadlines"],
        # swarmsight (ISSUE 13): per-family deadline-budget attribution
        # (where each family's end-to-end seconds went, by phase, with
        # the miss-table argmax) + the /api/fleet aggregate snapshot —
        # the observed data plane the item-5 autoscaler will consume
        "budget_attribution": report["budget_attribution"],
        "fleet": report["fleet"],
        # the satellite's tuning story: sweep tables + the winners the
        # shipped defaults were landed from
        "sweeps": {
            "lane_gains": loadgen.sweep_lane_gains(seed),
            "prefetch_window": loadgen.sweep_prefetch_window(seed),
            # ISSUE 10: the derivation DEFAULT_FAMILY_DEADLINES ships
            # (pinned defaults == winner, tests/test_loadgen.py)
            "deadline_table": loadgen.sweep_deadline_table(seed),
        },
    }


def _bench_ring_flash(*, on_tpu: bool, iters: int) -> dict:
    """ISSUE 18 (swarmkernel): the fused ring-flash attainment row.

    Times the seq-parallel self-attention shard_map both ways — the
    ppermute ring scan (the exactness oracle) and the fused Pallas
    ring-flash kernel — on the same mesh and shapes, and stamps each
    kind's p50, static roofline (attainment vs measured p50) and HLO
    collective census. On a TPU pod the delta IS the DMA/compute
    overlap; on CPU hosts the fused kind rides Pallas interpret mode,
    so the speedup number is notional there while the census (the
    zero-spurious-all-reduce acceptance line) and parity stay exact."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": "needs >= 2 devices for a seq mesh",
                "devices": len(devices)}
    sp = 4 if len(devices) >= 4 else len(devices)

    from functools import partial

    from jax.sharding import PartitionSpec as P

    from chiaswarm_tpu.analysis import hlocheck
    from chiaswarm_tpu.core.compat import shard_map, shard_map_unchecked
    from chiaswarm_tpu.core.mesh import MeshSpec, build_mesh
    from chiaswarm_tpu.obs import hlocost
    from chiaswarm_tpu.ops.ring_flash_attention import ring_flash_attention
    from chiaswarm_tpu.parallel.ring_attention import ring_attention

    mesh = build_mesh(MeshSpec({"seq": sp}), devices=devices[:sp])
    # TPU: the SDXL 1024px self-attention class the kernel targets;
    # CPU: the tiny hermetic shape (interpret mode is O(slow))
    b, l, h, d = (2, 4096, 10, 64) if on_tpu else (2, 128, 2, 32)
    spec = P(None, "seq", None, None)
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, l, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, l, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, l, h, d), jnp.float32)

    kinds = {
        "ring": shard_map(partial(ring_attention, axis_name="seq"),
                          mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec),
        "ring_flash": shard_map_unchecked(
            partial(ring_flash_attention, axis_name="seq",
                    mesh_axis_names=tuple(mesh.axis_names)),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec),
    }
    out: dict = {"mesh": {"seq": sp}, "shape": [b, l, h, d]}
    for kind, fn in kinds.items():
        jitted = jax.jit(fn)
        compiled = jitted.lower(q, k, v).compile()
        compiled(q, k, v).block_until_ready()  # warm
        times = []
        for _ in range(max(2, iters)):
            t0 = time.perf_counter()
            compiled(q, k, v).block_until_ready()
            times.append(time.perf_counter() - t0)
        p50 = _percentile50(times)
        hlo = hlocost.compiled_hlo_text(compiled)
        row = {"p50_latency_s": round(p50, 5)}
        if hlo:
            row["roofline"] = hlocost.static_program_report(
                hlo, achieved_s=p50)
            # the ISSUE-18 acceptance line: the fused program's census
            # must show the collective-permute ring and ZERO spurious
            # all-reduces (an all-reduce here = the softmax combine
            # leaked out of the carried state — R11's runtime face)
            row["hlo_contract"] = hlocheck.census(hlo)
        out[kind] = row
    out["speedup_ring_flash_vs_ring"] = round(
        out["ring"]["p50_latency_s"]
        / max(out["ring_flash"]["p50_latency_s"], 1e-9), 4)
    return out


def _bench_federated_load(*, on_tpu: bool, attn: str) -> dict:
    """ISSUE 18 satellite: the federated hive (PR 17) under the same
    seeded diurnal overload as ``load_harness``, but sharded across a
    3-shard control plane with multiplexed workers — stamps the
    fleet-wide end-to-end p50/p99 and the cross-shard steal books so
    BENCH rounds track whether work stealing keeps shard queues level
    (steals_total == 0 would mean the empty-poll steal seam went
    dead). Control-plane only: identical on CPU and TPU hosts."""
    import asyncio

    from chiaswarm_tpu.node import loadgen

    seed = "swarmfed"  # FIXED, same stance as load_harness
    schedule = loadgen.build_scenario(seed=seed, n_users=1000,
                                      duration_s=2.5, rate_jobs_s=120)
    report = asyncio.run(loadgen.run_load(
        schedule, n_workers=3, n_shards=3, seed=seed, lease_s=3.0,
        max_jobs_per_poll=4, settle_timeout_s=180))
    hive = report["hive"]
    return {
        "seed": seed,
        "n_shards": hive["n_shards"],
        "offered": report["offered"],
        "outcomes": report["outcomes"],
        "zero_loss": report["reconciliation"]["zero_loss"],
        "admitted_p99_within_deadline":
            report["admitted_deadline"]["p99_within_deadline"],
        # fleet-wide latency: per-workload {p50, p99, n} end-to-end
        "latency_s": report["latency_s"]["end_to_end"],
        # cross-shard steal books, counted once by their owning shard
        "steals_total": hive["aggregate"]["steals_total"],
        "steals": hive["aggregate"]["steals"],
        "forwarded_uploads": hive["aggregate"]["forwarded_uploads"],
        "per_shard_completed": [s["completed"] for s in hive["shards"]],
        "fleet": report["fleet"],
    }


def _bench_autoscaler(*, on_tpu: bool, attn: str) -> dict:
    """ISSUE 19 (swarmplan): THE autoscaler headline — the same seeded
    diurnal curve (one spike window) driven once under the
    capacity-model planner (fleet starts at 1 worker, grows/shrinks per
    planning tick) and once per static roster size, with worker-hours
    accounted identically for both. The stamped claim: the
    planner-tracked fleet holds zero loss and bounded admitted p99 with
    STRICTLY fewer worker-hours than every feasible static roster in
    the swept set. Control-plane only: identical on CPU and TPU hosts."""
    import asyncio

    from chiaswarm_tpu.node import loadgen

    seed = "swarmplan"  # FIXED, same stance as load_harness
    population = loadgen.UserPopulation(n_users=200, seed=seed)
    curve = loadgen.DiurnalCurve(amplitude=0.8, spikes=1,
                                 spike_mult=2.0, seed=seed)
    schedule = loadgen.generate_schedule(
        population, curve, duration_s=12.0, rate_jobs_s=90.0,
        seed=seed, id_prefix="plan")
    plan = loadgen.AutoscalePlan(
        min_workers=1, max_workers=5, tick_every_s=0.2,
        capacity_jobs_s_per_worker=40.0, backlog_drain_s=1.5,
        cooldown_up_s=0.4, cooldown_down_s=2.0, smoothing_window_s=1.5)
    table = asyncio.run(loadgen.autoscale_comparison(
        schedule, autoscale=plan, static_rosters=[1, 2, 3, 4, 5],
        seed=seed, settle_timeout_s=180))
    auto = table["planner_report"]["autoscale"]
    return {
        "seed": seed,
        "offered": table["planner_report"]["offered"],
        "planner": table["planner"],
        "static": table["static"],
        "gate": table["gate"],
        "events": auto["events"],
        "fleet_size_series": auto["sizes"],
        "final_decision": auto["decision"],
        "contention": table["planner_report"]["contention"],
    }


def run_configs(names: list[str], *, on_tpu: bool, iters: int,
                attn: str) -> dict:
    import jax
    import numpy as np

    from chiaswarm_tpu.pipelines.components import Components, ControlNetBundle
    from chiaswarm_tpu.pipelines.diffusion import DiffusionPipeline

    device = jax.devices()[0]

    def components(family: str) -> Components:
        c = Components.random(family, seed=0)
        c.params = jax.device_put(c.params, device)
        return c

    rng = np.random.default_rng(0)
    results: dict[str, dict] = {}

    if "sd15" in names:
        # BASELINE.json #1: SD 1.5 txt2img, 512x512, 20 DDIM steps
        pipe = DiffusionPipeline(components("sd15" if on_tpu else "tiny"),
                                 attn_impl=attn)
        size = 512 if on_tpu else 64
        results["sd15_txt2img_512_ddim20"] = _bench_diffusion(
            pipe, size=size, steps=20 if on_tpu else 2, batch=1,
            iters=iters, scheduler="ddim", pipelined=True)
        del pipe

    if "sd21" in names:
        # BASELINE.json #2: SD 2.1 img2img + inpainting
        c = components("sd21" if on_tpu else "tiny")
        pipe = DiffusionPipeline(c, attn_impl=attn)
        size = 512 if on_tpu else 64
        steps = 30 if on_tpu else 2
        init = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
        results["sd21_img2img_512"] = _bench_diffusion(
            pipe, size=size, steps=steps, batch=1, iters=iters,
            init_image=init, pipelined=True)
        half_mask = np.zeros((size, size), np.float32)
        half_mask[size // 2:] = 1.0
        results["sd21_inpaint_512"] = _bench_diffusion(
            pipe, size=size, steps=steps, batch=1, iters=iters,
            init_image=init, mask=half_mask, pipelined=True)
        if on_tpu:
            # SD 2.1's PUBLISHED serving shape: the 768-v checkpoint is
            # native 768px (the reference serves it there; its 9216-token
            # attention level tiles exactly with the 1536 flash block)
            results["sd21_txt2img_768"] = _bench_diffusion(
                pipe, size=768, steps=steps, batch=1, iters=iters,
                pipelined=True)
        del pipe, c

    if "controlnet" in names:
        # BASELINE.json #4: ControlNet + SDXL
        fam = "sdxl" if on_tpu else "tiny"
        c = components(fam)
        bundle = ControlNetBundle.random(fam, seed=1)
        bundle.params = jax.device_put(bundle.params, device)
        pipe = DiffusionPipeline(c, attn_impl=attn)
        size = 1024 if on_tpu else 64
        cond = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
        results["controlnet_sdxl_1024"] = _bench_diffusion(
            pipe, size=size, steps=30 if on_tpu else 2, batch=1,
            iters=iters, controlnet=bundle, control_image=cond,
            pipelined=True)
        del pipe, c, bundle

    if "img2vid" in names:
        # BASELINE.json #5 names "Stable Video Diffusion img2vid": the
        # image-conditioned SVD-class family (pipelines/video.py::SVD)
        from chiaswarm_tpu.pipelines.video import (
            Img2VidPipeline,
            VideoComponents,
        )

        fam = "svd_img2vid" if on_tpu else "tiny_svd"
        vc = VideoComponents.random(fam, seed=0)
        vc.params = jax.device_put(vc.params, device)
        ipipe = Img2VidPipeline(vc, attn_impl=attn)
        frames = 14 if on_tpu else 8
        steps = 25 if on_tpu else 2
        # recorded shape = the PUBLISHED SVD serving portrait (576x1024,
        # 14 frames, 25 steps — VERDICT r4 #6); the square 512 bucket
        # stays as a secondary entry for cross-round continuity
        shapes = ([("img2vid_svd", 576, 1024),
                   ("img2vid_svd_512", 512, 512)] if on_tpu
                  else [("img2vid_svd", 64, 64)])
        for name, bh, bw in shapes:
            cond = rng.integers(0, 255, (bh, bw, 3), dtype=np.uint8)

            def irun(seed: int) -> float:
                t0 = time.perf_counter()
                out, _ = ipipe(cond, num_frames=frames, steps=steps,
                               height=bh, width=bw, seed=seed)
                assert out.shape[0] == frames
                return time.perf_counter() - t0

            irun(0)
            times = [irun(i + 1) for i in range(iters)]
            p50 = _percentile50(times)
            results[name] = {
                "p50_latency_s": round(p50, 3),
                "frames": frames,
                "steps": steps,
                "size": [bh, bw],
                "frames_per_sec": round(frames / p50, 4),
            }
        del ipipe, vc

    if "stepper" in names:
        # ISSUE 3: steady-state throughput under staggered mixed-steps
        # arrivals — continuous step-level admission vs the burst path
        results["stepper_mixed_arrival"] = _bench_mixed_arrival(
            on_tpu=on_tpu, attn=attn)

    if "stepper_mixed_workloads" in names:
        # ISSUE 7: adaptive-width lanes under a staggered txt2img +
        # img2img + inpaint stream vs those jobs' per-job solo paths
        results["stepper_mixed_workloads"] = _bench_mixed_workloads(
            on_tpu=on_tpu, attn=attn)

    if "step_collapse" in names:
        # ISSUE 12 (swarmturbo): few-step sampling + DeepCache feature
        # reuse — the per-image-math configs of the 15x-gap arc, with
        # UNet-eval reductions and the PSNR/SSIM quality gate stamped
        results.update(_bench_step_collapse(on_tpu=on_tpu, attn=attn,
                                            iters=iters))

    if "txt2vid" in names:
        # the model class the reference actually serves for video
        # (ModelScope-class temporal UNet, swarm/video/tx2vid.py)
        from chiaswarm_tpu.pipelines.video import (
            VideoComponents,
            VideoPipeline,
        )

        fam = "modelscope_t2v" if on_tpu else "tiny_vid"
        vc = VideoComponents.random(fam, seed=0)
        vc.params = jax.device_put(vc.params, device)
        vpipe = VideoPipeline(vc, attn_impl=attn)
        frames = 16 if on_tpu else 8
        steps = 25 if on_tpu else 2
        size = 256 if on_tpu else 64

        def vrun(seed: int) -> float:
            t0 = time.perf_counter()
            out, _ = vpipe("a paper boat drifting", num_frames=frames,
                           steps=steps, height=size, width=size, seed=seed)
            assert out.shape[0] == frames
            return time.perf_counter() - t0

        vrun(0)
        times = [vrun(i + 1) for i in range(iters)]
        p50 = _percentile50(times)
        results["txt2vid_modelscope"] = {
            "p50_latency_s": round(p50, 3),
            "frames": frames,
            "steps": steps,
            "size": size,
            "frames_per_sec": round(frames / p50, 4),
        }
        del vpipe, vc

    if "model_churn" in names:
        # ISSUE 8: swap latency + resident-model count under a tight
        # residency budget (the ledger's BENCH headline)
        results["model_churn"] = _bench_model_churn(on_tpu=on_tpu,
                                                    attn=attn)

    if "load_harness" in names:
        # ISSUE 9: the swarmload capacity model (jobs/s/chip per
        # workload mix), overload-control outcomes under scripted 10x
        # + worker kill, and the gain/prefetch sweep tables
        results["load_harness"] = _bench_load_harness(on_tpu=on_tpu,
                                                      attn=attn)

    if "ring_flash" in names:
        # ISSUE 18 (swarmkernel): fused ring-flash vs ppermute ring —
        # per-kind p50, roofline attainment, HLO collective census
        results["ring_flash"] = _bench_ring_flash(on_tpu=on_tpu,
                                                  iters=iters)

    if "federated_load" in names:
        # ISSUE 18 satellite: the 3-shard federated hive under the
        # seeded overload — fleet p50/p99 + cross-shard steal books
        results["federated_load"] = _bench_federated_load(on_tpu=on_tpu,
                                                          attn=attn)

    if "autoscaler" in names:
        # ISSUE 19 (swarmplan): planner-tracked fleet vs the static
        # roster sweep — worker-hours at equal-or-better service
        results["autoscaler"] = _bench_autoscaler(on_tpu=on_tpu,
                                                  attn=attn)

    return results


def main() -> None:
    import jax

    from chiaswarm_tpu.core.compile_cache import (
        enable_persistent_compilation_cache,
    )

    # same persistent compile cache, same placement rule, as the worker
    enable_persistent_compilation_cache()
    # the worker's startup knob (node/worker.py startup) — bench must
    # measure the same numerics the serving path runs
    jax.config.update("jax_default_matmul_precision", "bfloat16")

    from chiaswarm_tpu.pipelines.components import Components
    from chiaswarm_tpu.pipelines.diffusion import DiffusionPipeline

    on_tpu = jax.default_backend() == "tpu"
    family = os.environ.get(
        "CHIASWARM_BENCH_FAMILY", "sdxl" if on_tpu else "tiny"
    )
    size = int(os.environ.get("CHIASWARM_BENCH_SIZE",
                              "1024" if on_tpu else "64"))
    steps = int(os.environ.get("CHIASWARM_BENCH_STEPS",
                               "30" if on_tpu else "4"))
    batch = int(os.environ.get("CHIASWARM_BENCH_BATCH", "1"))
    iters = int(os.environ.get("CHIASWARM_BENCH_ITERS", "3"))
    attn = os.environ.get("CHIASWARM_BENCH_ATTN", "auto")
    which = os.environ.get("CHIASWARM_BENCH_CONFIGS", "all")

    # ---- headline: the north-star config ----
    c = Components.random(family, seed=0)
    c.params = jax.device_put(c.params, jax.devices()[0])
    pipe = DiffusionPipeline(c, attn_impl=attn)
    headline = _bench_diffusion(pipe, size=size, steps=steps, batch=batch,
                                iters=iters, pipelined=True)
    del pipe, c

    # steady-state (transfer-overlapped) throughput is the serving number
    imgs_per_sec = headline.get("images_per_sec_pipelined",
                                headline["images_per_sec"])

    configs = {"sdxl_txt2img_1024": headline}
    if which != "headline":
        names = (["sd15", "sd21", "controlnet", "img2vid", "stepper",
                  "stepper_mixed_workloads", "step_collapse", "txt2vid",
                  "model_churn", "load_harness", "ring_flash",
                  "federated_load", "autoscaler"]
                 if which == "all" else which.split(","))
        configs.update(run_configs(names, on_tpu=on_tpu, iters=iters,
                                   attn=attn))

    # swarmscope snapshot (chiaswarm_tpu/obs): compile counts/durations
    # and lane step-latency histograms ride along with every BENCH run,
    # so a perf regression can be split into "got slower" vs "started
    # recompiling" without rerunning anything
    from chiaswarm_tpu.obs.metrics import REGISTRY
    from chiaswarm_tpu.serving.guard import suggest_hang_budget

    target = 4.0  # images/sec/chip, BASELINE.json north star
    print(json.dumps({
        "metric": f"{family} {size}px txt2img {steps} steps, images/sec/chip",
        "value": round(imgs_per_sec, 4),
        "unit": "images/sec/chip",
        "vs_baseline": round(imgs_per_sec / target, 4),
        "p50_latency_s": headline["p50_latency_s"],
        "batch": batch,
        "attn": attn,
        "backend": jax.default_backend(),
        "configs": configs,
        # swarmlens (ISSUE 11): whole-run lane step-seconds percentiles
        # + the MEASURED watchdog-budget suggestion they imply — the
        # numbers that graduate the PR-10 hang-budget priors
        "step_seconds_percentiles": _step_seconds_snapshot(),
        "suggested_hang_budget": suggest_hang_budget(),
        "metrics": REGISTRY.snapshot(),
    }))


if __name__ == "__main__":
    main()
