#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the worker still starts on the chip.

    python3 chip_smoke.py [--chips N]

One process, run from the root of a checkout (no install step, no network,
no git). It drives the path every hive job takes — in-process hive ->
``node/worker.py`` -> ``node/executor.py`` -> ``serving/stepper.py`` lanes
-> PNG artifacts — ONCE, at the full width and depth of SDXL at 1024x1024
with seeded random weights, and checks what comes out. In order:

1. device: jax must report a TPU (a shell that says ``JAX_PLATFORMS=cpu``
   is a failure, not a mode);
2. kernel pre-flight: the Pallas flash kernel compiles under Mosaic and
   agrees with the einsum reference at SDXL's two self-attention shapes;
3. main path: two waves of three txt2img jobs (steps 30, 30, 20 — only
   lanes can merge mixed step counts) through a real ``Worker``, with the
   residency ledger filled to its default budget first, so the lane and
   the decode run above as many resident bytes as the worker will ever
   hold by itself;
4. asserts, each fatal: every job ok with a lane stamp and a finite,
   non-constant 1024x1024 PNG whose sha256 matches; every lane step
   program holds Mosaic custom calls for its flash self-attentions; wave
   2 compiles nothing; nothing was evicted; no OOM halving, watchdog
   condemnation or timeout; the native codec loaded; the worker drains
   and the lanes stop.

No phase sits in a try/except: the first failure is the exit, and nothing
is printed to stdout. On success stdout holds exactly two lines, each one
JSON object. The first, ``{"setup": {...}, "sanity": {...}}``, carries the
set-up facts and sanity values of this run — NOT benchmark metrics (the
repo's speed numbers come from the benchmark, not from here). The LAST is
the verdict and nothing else: ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``, the device as jax reports it.

``--chips N`` (N > 1) runs the same waves on the worker's own default pool
over a host of at least N devices and additionally checks that every
device holds param shards and ran the lane program.
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import hashlib
import io
import json
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT_DIR = HERE / "chiprun_out" / "chip_smoke"

#: SDXL's two self-attention shapes at 1024 px as (B, L, H, D): the CFG
#: pair of one row at the 64x64 level (10 heads) and the 32x32 level (20)
SDXL_ATTN_SHAPES = ((2, 4096, 10, 64), (2, 1024, 20, 64))
#: Flash vs einsum on bf16 inputs, as max|diff| / max|reference|. Both
#: accumulate in fp32 and round the output to bf16 once (half an ulp,
#: 2^-9 relative); the einsum reference also rounds its softmax weights to
#: bf16 before the PV product. A few ulps of the largest output bound both
#: — an indexing, masking or rescale bug moves the output by O(1).
ATTN_TOLERANCE = 2.0 ** -6
#: per wave; two rows retire on one boundary, the third earlier
WAVE_STEPS = (30, 30, 20)
#: the ledger entry that fills the residency budget (see fill_residency)
BALLAST = "chip-smoke/ballast"


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class SmokeFailure(AssertionError):
    """A check of this script did not hold."""


def require(ok, why) -> None:
    """Fatal check (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise SmokeFailure(why)


def device_facts(require_tpu: bool, chips: int) -> dict:
    """Phase 1. Sets no platform: jax picks, and anything but a TPU is
    the failure this script exists to catch."""
    import importlib.metadata as md

    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if require_tpu and dev0.platform != "tpu":
        log(f"no TPU: jax.devices() = {devices}, JAX_PLATFORMS = "
            f"{os.environ.get('JAX_PLATFORMS')!r}")
        raise SystemExit(2)
    if len(devices) < chips:
        log(f"--chips {chips}: jax reports {len(devices)}: {devices}")
        raise SystemExit(2)
    stats = dev0.memory_stats() or {}
    require(stats.get("bytes_limit") or dev0.platform != "tpu",
            f"{dev0} reports no memory_stats()['bytes_limit']: {stats}")
    facts = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(devices),
        "versions": {dist: md.version(dist)
                     for dist in ("jax", "jaxlib", "libtpu", "flax")},
        "bytes_limit": stats.get("bytes_limit"),
    }
    log(f"device: {facts}")
    return facts


def kernel_preflight(shapes) -> list[dict]:
    """Phase 2: ``flash_attention`` (Mosaic on TPU, Pallas interpret
    elsewhere) against ``ops.attention._xla_attention`` on seeded normal
    bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chiaswarm_tpu.ops.attention import _xla_attention
    from chiaswarm_tpu.ops.flash_attention import flash_attention

    rows = []
    for i, shape in enumerate(shapes):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(i), 3)
        q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
                   for key in (kq, kk, kv))
        scale = float(shape[-1]) ** -0.5
        got = np.asarray(flash_attention(q, k, v, scale=scale), np.float32)
        want = np.asarray(
            jax.jit(_xla_attention, static_argnums=3)(q, k, v, scale),
            np.float32)
        require(got.shape == tuple(shape) and np.isfinite(got).all(),
                f"flash output at {shape}: shape {got.shape} or non-finite")
        err = float(np.abs(got - want).max() / np.abs(want).max())
        rows.append({"shape": list(shape), "rel_max_err": round(err, 6)})
        require(err <= ATTN_TOLERANCE,
                f"flash kernel disagrees with the einsum reference at "
                f"{shape}: max|diff|/max|ref| = {err:.4g} > "
                f"{ATTN_TOLERANCE:.4g}")
    log(f"kernel pre-flight: {rows}")
    return rows


def fill_residency(registry, family: str, mesh) -> int:
    """Make the ledger FULL before the first job: a resident entry of
    (default budget - the model's footprint estimate) bytes on every chip
    of the slot. What the worker may keep resident by itself is a share
    of HBM chosen in ``core/mesh.py``; this is what holds that share to
    the chip — a lane or decode that no longer fits above a full ledger
    fails here (OOM halving, an error envelope) instead of in service.
    The model's own load must then fit EXACTLY, evicting nothing."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from chiaswarm_tpu.pipelines.components import (
        estimate_family_bytes,
        measured_param_bytes,
    )

    ledger = registry.residency
    rows = (ledger.budget_bytes - estimate_family_bytes(family)) // 4096
    require(rows > 0, f"{family} alone exceeds the residency budget "
                      f"({ledger.budget_bytes} bytes)")
    ledger.acquire(
        BALLAST,
        lambda: jax.device_put(np.zeros((rows, 1024), np.float32),
                               NamedSharding(mesh, PartitionSpec())),
        model=BALLAST, size_of=measured_param_bytes)
    return rows * 4096


def check_result(result: dict, size: int) -> dict:
    """One uploaded envelope: ok, lane-stamped, and a real image."""
    import numpy as np
    from PIL import Image

    from chiaswarm_tpu.node.minihive import result_error_kind

    cfg, job = result["pipeline_config"], result["id"]
    require("error" not in cfg and "fatal_error" not in result
            and result_error_kind(result) is None,
            f"job {job} came back as an error envelope: {cfg}")
    stamp = cfg.get("stepper")
    require(stamp and stamp.get("lane") is not None,
            f"job {job} fell back to the per-job path: {cfg}")
    artifact = result["artifacts"]["primary"]
    blob = base64.b64decode(artifact["blob"])
    require(hashlib.sha256(blob).hexdigest() == artifact["sha256_hash"],
            f"job {job}: sha256 mismatch")
    image = Image.open(io.BytesIO(blob))
    require(image.format == "PNG" and image.size == (size, size),
            f"job {job}: {image.format} {image.size}")
    pixels = np.asarray(image.convert("RGB"), np.float32)
    require(np.isfinite(pixels).all() and pixels.std() > 1.0,
            f"job {job}: constant image (std {pixels.std():.3f})")
    return {"id": result["id"], "lane": stamp["lane"],
            "lane_width": stamp["lane_width"],
            "pixel_std": round(float(pixels.std()), 2)}


async def settle(hive, run: asyncio.Task, n_total: int,
                 timeout: float) -> dict[str, float]:
    """Wait until the hive holds ``n_total`` results; arrival clock per
    job id for the ones that landed during this wait. A worker that
    stops first is the failure — its exception, not a timeout."""
    arrived: dict[str, float] = {}
    seen = len(hive.results)
    deadline = time.monotonic() + timeout

    while seen < n_total:
        hive.result_event.clear()
        if len(hive.results) == seen:
            waiter = asyncio.ensure_future(hive.result_event.wait())
            done, _ = await asyncio.wait(
                {waiter, run}, timeout=deadline - time.monotonic(),
                return_when=asyncio.FIRST_COMPLETED)
            waiter.cancel()
            if run in done:
                run.result()
            require(waiter in done,
                    f"{len(hive.results)} of {n_total} results after "
                    f"{timeout:.0f}s (worker stopped: {run.done()})")
        now = time.monotonic()
        for result in hive.results[seen:]:
            arrived[str(result["id"])] = now
        seen = len(hive.results)
    return arrived


async def drive(worker, hive, capture, model: str, size: int,
                steps) -> dict:
    """Phase 3: two waves through ``Worker.run()``, then a graceful stop."""
    from chiaswarm_tpu.obs.metrics import REGISTRY

    def wave(n: int) -> list[dict]:
        return [{"id": f"w{n}-{i}", "model_name": model,
                 "prompt": f"chip smoke wave {n} prompt {i}",
                 "seed": 100 * n + i, "num_inference_steps": count,
                 "guidance_scale": 7.5, "height": size, "width": size,
                 "content_type": "image/png"}
                for i, count in enumerate(steps)]

    def compiles() -> tuple[dict, int]:
        # the counter sees first calls of compile-cache entries; the
        # capture sees every new input signature of an entry that exists
        # (what plain jit would answer with a silent retrace)
        counters = REGISTRY.snapshot()["chiaswarm_compiles_total"]["values"]
        return dict(counters), len(capture.executables)

    run = asyncio.create_task(worker.run())
    t0 = time.monotonic()
    for job in wave(1):
        hive.submit(job)
    await settle(hive, run, len(steps), timeout=900.0)
    wave1_s = time.monotonic() - t0
    log(f"wave 1 settled in {wave1_s:.1f}s (cold: compiles included)")

    compiles_before = compiles()
    t1 = time.monotonic()
    for job in wave(2):
        hive.submit(job)
    arrived = await settle(hive, run, 2 * len(steps), timeout=300.0)
    compiles_after = compiles()
    require(compiles_after == compiles_before,
            f"wave 2 compiled (counters, programs): {compiles_before} -> "
            f"{compiles_after}")

    health = worker.health()
    worker.request_stop()
    await asyncio.wait_for(run, timeout=120.0)
    return {"wave1_s": round(wave1_s, 1),
            "wave2_job_s": {job_id: round(t - t1, 2)
                            for job_id, t in sorted(arrived.items())},
            "compiles": compiles_after[0], "health": health}


def run_smoke(family: str, size: int, *, require_tpu: bool, chips: int = 1,
              steps=WAVE_STEPS, attn_shapes=SDXL_ATTN_SHAPES,
              out_dir: Path = OUT_DIR) -> dict:
    """The whole smoke for one family at one size; returns the result
    object. ``__main__`` always calls it with SDXL, 1024 px and
    ``require_tpu=True``; the tier-1 test drives it at ``tiny``/64 px on
    the CPU with ``require_tpu=False``."""
    device = device_facts(require_tpu, chips)

    import threading

    import jax

    from chiaswarm_tpu import native
    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.compile_cache import (
        enable_persistent_compilation_cache,
    )
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.registry import ModelRegistry
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.obs.hlocost import (
        ProgramCapture,
        compiled_hlo_text,
        parse_hlo_text,
    )
    from chiaswarm_tpu.obs.metrics import REGISTRY
    from chiaswarm_tpu.pipelines import diffusion as diffusion_mod
    from chiaswarm_tpu.serving.residency import ResidencyManager

    cache_dir = Path(enable_persistent_compilation_cache())

    def cache_entries() -> int:  # jax creates the directory on first write
        return len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0

    cache_before = cache_entries()

    attn = kernel_preflight(attn_shapes)

    # ---- main path ------------------------------------------------------
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    os.environ["SWARM_TPU_ROOT"] = str(out_dir / "root")
    model = f"smoke/{family}"
    # a ledger made like the process-wide default one, but after the
    # settings root moved (a test process may hold an older default)
    registry = ModelRegistry(catalog=[{"name": model, "family": family}],
                             allow_random=True, residency=ResidencyManager())
    # one chip is the shape every number in ROADMAP assumes, whatever the
    # host holds; N > 1 is the stock worker's own default pool
    pool = (ChipPool(n_slots=1, devices=jax.devices()[:1])
            if chips == 1 else None)
    capture = ProgramCapture()

    async def scenario() -> dict:
        # the lease outlives a cold compile, so nothing is redelivered
        hive = MiniHive(lease_s=1200.0, delay_s=0.0)
        uri = await hive.start()
        worker = Worker(
            settings=Settings(
                hive_uri=uri, hive_token="chip-smoke",
                worker_name="chip-smoke",
                # set-up, not serving policy: poll fast, and leave a cold
                # compile inside the job budget so a timeout envelope can
                # only mean a hang
                poll_busy_s=0.25, poll_idle_s=0.25, job_deadline_s=1000.0,
                install_signal_handlers=False),
            registry=registry, pool=pool)
        ballast = fill_residency(registry, family, worker.pool.slots[0].mesh)
        driven = await drive(worker, hive, capture, model, size, steps)
        await hive.stop()
        return {"hive": hive, "worker": worker, "ballast": ballast,
                **driven}

    with capture.patching(diffusion_mod):
        ran = asyncio.run(scenario())
    hive, worker, health = ran["hive"], ran["worker"], ran["health"]

    # ---- asserts --------------------------------------------------------
    require(len(hive.results) == 2 * len(steps), hive.uploaded_ids())
    jobs = [check_result(result, size) for result in hive.results]
    require(len({job["id"] for job in jobs}) == 2 * len(steps), jobs)

    for key in ("jobs_timed_out", "jobs_retried", "jobs_failed",
                "dead_letter_depth"):
        require(health[key] == 0, f"worker health: {key} = {health[key]}")
    for key in ("hangs", "condemned_lanes", "invalid_outputs"):
        require(health["guard"][key] == 0, f"guard: {health['guard']}")
    for key in ("lanes_failed", "lanes_condemned", "rows_failed",
                "rows_invalid"):
        require(not health["stepper"].get(key),
                f"lanes: {key} in {health['stepper']}")
    steppers = [slot._stepper for slot in worker.pool]
    require(not any(st._width_limits for st in steppers),
            "a lane OOM halved the lane width")
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith("stepper-lane-")]
    require(not alive and all(st.stats()["lanes_live"] == 0
                              for st in steppers),
            f"lanes outlived the worker's drain: {alive}")
    require(native.load() is not None, "native codec not loaded")
    ledger = registry.residency.snapshot()
    require(ledger["resident_models"] == sorted([BALLAST, model])
            and not ledger["evictions"] and not ledger["degraded_loads"],
            f"the full ledger did not hold: {ledger}")

    # the census obs/hlocost already takes of a compiled program: every
    # lane step executable this run built, flash custom calls counted
    flash_calls = []
    with open(out_dir / "mosaic_calls.txt", "w") as dump:
        for i, compiled in enumerate(capture.executables):
            text = compiled_hlo_text(compiled)
            costs = parse_hlo_text(text).values()
            flash_calls.append(
                sum(1 for cost in costs if cost["kind"] == "flash"))
            dump.writelines(f"program {i}: {line.strip()[:400]}\n"
                            for line in text.splitlines()
                            if "tpu_custom_call" in line)
    if device["platform"] == "tpu":
        # only the UNet step programs (one per lane width) attend over
        # enough tokens for ops.attention's auto pick to take the kernel
        step_programs = int(ran["compiles"]["stepper_step"])
        require(sum(1 for n in flash_calls if n) >= step_programs >= 1,
                f"{step_programs} lane step programs compiled, flash "
                f"custom calls by program: {flash_calls}")

    mesh = worker.pool.slots[0].mesh
    pool_devices = list(mesh.devices.flatten())
    if chips > 1:
        pipe = registry.pipeline(model, mesh=mesh)
        holders = {shard.device
                   for leaf in jax.tree.leaves(pipe.c.params)
                   for shard in leaf.addressable_shards}
        require(holders == set(pool_devices),
                f"param shards on {holders}, pool is {pool_devices}")
        for dev in pool_devices:
            # activations above the resident shards: the device ran work
            stats = dev.memory_stats()
            if stats or dev.platform == "tpu":
                require(stats and (stats["peak_bytes_in_use"]
                                   > stats["bytes_in_use"]),
                        f"{dev} never held more than its params: {stats}")

    snapshot = REGISTRY.snapshot()
    compile_s = {tag: round(v["sum"], 2) for tag, v in
                 snapshot["chiaswarm_compile_seconds"]["values"].items()}
    result = {
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
        "setup": {
            "versions": device["versions"],
            "bytes_limit": device["bytes_limit"],
            "model": family, "size": size, "steps": list(steps),
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "chips_in_pool": len(pool_devices),
            "compile_seconds_by_tag": compile_s,
            "compile_seconds_total": round(sum(compile_s.values()), 2),
            "compiles_by_tag": ran["compiles"],
            "cache_dir": str(cache_dir),
            "cache_entries_before": cache_before,
            "cache_entries_after": cache_entries(),
            "flash_calls_by_program": flash_calls,
        },
        "sanity": {
            "jobs_ok": len(jobs), "jobs": jobs,
            "attention_preflight": attn,
            "wave1_seconds_cold": ran["wave1_s"],
            "wave2_job_seconds": ran["wave2_job_s"],
            "residency": {"budget_bytes": ledger["budget_bytes"],
                          "resident_bytes": ledger["resident_bytes"],
                          "ballast_bytes": ran["ballast"]},
            "peak_bytes_in_use": [
                (dev.memory_stats() or {}).get("peak_bytes_in_use")
                for dev in pool_devices],
        },
    }
    (out_dir / "result.json").write_text(json.dumps(result, indent=1))
    return result


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1,
                        help="1 = one-chip slot (default); N > 1 = the "
                             "worker's default pool on an N-device host")
    args = parser.parse_args(argv)
    result = run_smoke("sdxl", 1024, require_tpu=True, chips=args.chips)
    verdict = {key: result.pop(key) for key in ("ok", "device")}
    print(json.dumps(result))
    # the last line is the verdict alone: exactly ``ok`` and ``device``
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
