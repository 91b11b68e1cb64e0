"""One run of one cell: set-up, warm-up, the measured window, the check.

The system under test is driven only through its public entry: jobs go
to an in-process ``MiniHive``; a real ``Worker`` (one-slot ``ChipPool``
on the first device, its default ``ResidencyManager``, lanes on) polls,
runs them and uploads their artifacts. All times are on the hive's
clock (``time.monotonic``). Nothing here calls a pipeline, and nothing
here knows what a job is: the weights and the registry, the jobs, the
comparison, the program modules to capture and the work of a job are
the configuration's kind's (``perfbench/kinds/``).

Order of a run: imports -> device check -> seeded weights on the device
-> worker up -> warm-up of the mix's shapes (solo jobs, then one burst)
-> [window opens: ``setup_s`` ends] -> traffic for ``--seconds`` ->
[window closes] -> peak memory read -> worker drained and dropped ->
plain reference over a sample of the window's jobs -> result line.
"""

from __future__ import annotations

import asyncio
import gc
import importlib
import json
import os
import shutil
import sys
import time
from pathlib import Path

from perfbench import kinds
from perfbench import traffic as traffic_mod

ROOT = Path(__file__).resolve().parent.parent
#: everything a run leaves behind (trace dumps, the worker's root)
SCRATCH = ROOT / ".perfbench"

#: jax.monitoring duration events that mean "a program was compiled"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for: exit code
    2 and no result line."""

    def __init__(self, why: str) -> None:
        log(why)
        super().__init__(2)


def device_facts(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices() = {devices}, JAX_PLATFORMS = "
                     f"{os.environ.get('JAX_PLATFORMS')!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), jax reports "
                     f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (the repo's loadgen convention)."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no values")
    k = max(0, min(len(ordered) - 1,
                   int(-(-q * len(ordered) // 100)) - 1))
    return ordered[k]


class CompileCounter:
    """Counts program compilations, two independent ways: the program's
    own ``chiaswarm_compiles_total`` (first calls of its executable
    cache) and jax's backend-compile events (every XLA compile, eager
    ops included, whether or not the persistent cache served it)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name in COMPILE_EVENTS:
            self.events += 1

    def read(self) -> tuple[float, int]:
        from chiaswarm_tpu.obs.metrics import REGISTRY

        values = REGISTRY.snapshot()["chiaswarm_compiles_total"]["values"]
        return float(sum(values.values())), self.events


class Window:
    """Settlements as the hive saw them, in order."""

    def __init__(self, hive, run: asyncio.Task) -> None:
        self.hive, self.run, self.seen = hive, run, 0

    async def next_settled(self, timeout: float) -> list[dict]:
        """New settled jobs (each: id, t, ok, result); [] on timeout."""
        deadline = time.monotonic() + timeout
        while len(self.hive.results) == self.seen:
            self.hive.result_event.clear()
            if len(self.hive.results) != self.seen:
                break
            left = deadline - time.monotonic()
            if left <= 0:
                return []
            waiter = asyncio.ensure_future(self.hive.result_event.wait())
            done, _ = await asyncio.wait(
                {waiter, self.run}, timeout=left,
                return_when=asyncio.FIRST_COMPLETED)
            waiter.cancel()
            if self.run in done:
                self.run.result()  # the worker died: its exception
                raise RuntimeError("the worker stopped inside the run")
        from chiaswarm_tpu.node.minihive import result_error_kind

        fresh = []
        for result in self.hive.results[self.seen:]:
            job_id = str(result["id"])
            record = self.hive.flights.get(job_id) or {}
            settled = record.get("settled") or {}
            fresh.append({
                "id": job_id, "t": float(settled.get("t") or
                                         time.monotonic()),
                "ok": result_error_kind(result) is None
                and "error" not in (result.get("pipeline_config") or {}),
                "result": result, "record": record})
        self.seen = len(self.hive.results)
        return fresh

    async def settle_all(self, ids: set[str], timeout: float) -> list[dict]:
        out: list[dict] = []
        deadline = time.monotonic() + timeout
        pending = set(ids)
        while pending:
            fresh = await self.next_settled(deadline - time.monotonic())
            if not fresh:
                break
            for item in fresh:
                pending.discard(item["id"])
                out.append(item)
        return out


async def run_closed(win: Window, hive, clients: int, make, seconds: float):
    """Closed loop: ``clients`` jobs outstanding; each settlement sends
    the next. Opens at the first submission, closes at the first
    settlement at or after ``seconds``."""
    sent: dict[str, dict] = {}
    index = 0
    t_open = time.monotonic()
    for _ in range(clients):
        job = make(index)
        sent[job["id"]] = {"job": job, "due": time.monotonic()}
        hive.submit(job)
        index += 1
    settled: list[dict] = []
    t_close = None
    while t_close is None:
        fresh = await win.next_settled(600.0)
        if not fresh:
            raise RuntimeError("no settlement in 600 s")
        for item in fresh:
            if item["id"] not in sent:
                continue
            settled.append(item)
            if item["t"] - t_open >= seconds:
                t_close = item["t"]
                break
            job = make(index)
            sent[job["id"]] = {"job": job, "due": time.monotonic()}
            hive.submit(job)
            index += 1
    settled = [s for s in settled if s["t"] <= t_close]
    return {"t_open": t_open, "t_close": t_close, "sent": sent,
            "settled": settled, "attempted": len(settled)}


def run_control(*, workload: dict, config: dict, mix: dict, seed: int,
                n_jobs: int, require_tpu: bool = True) -> dict:
    """The comparison that has to fail, at the cell's own size: no
    worker; the window's first ``n_jobs`` jobs as the reference one
    precision down would have served them, through the kind's ``check``.
    The result has a run's keys, ``correct`` false if the limit holds."""
    import jax

    from chiaswarm_tpu.core.compile_cache import (
        enable_persistent_compilation_cache,
    )

    kind = kinds.of(config)
    device = device_facts(int(workload["chips"]), require_tpu)
    enable_persistent_compilation_cache()
    dev0 = jax.devices()[0]
    params = kind.seeded_params(config, seed, dev0)
    work = traffic_mod.units(mix, kind.UNIT, n_jobs, seed)
    jobs = [traffic_mod.make_job(kind, i, work[i], seed, config,
                                 f"bench/{config['name']}")
            for i in range(n_jobs)]
    verdict = kind.control(params, config, jobs, seed=seed)
    for row in verdict["jobs"]:
        log(f"control {verdict['precision']} {row}")
    stats = dev0.memory_stats() or {}
    return {"correct": bool(verdict["ok"]), "attempted": n_jobs,
            "failed": 0, "metrics": {},
            "device": dict(device, memory_peak_bytes=stats.get(
                "peak_bytes_in_use")),
            "control": verdict["precision"],
            "compared": verdict["numbers"]}


def run_cell(*, workload: dict, config: dict, mix: dict, benchmark: dict,
             seed: int, seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True, compare_jobs: int | None = None,
             out=sys.stdout) -> dict:
    """The whole run; returns the result object (also printed by the
    caller as the last line)."""
    split: dict[str, float] = {}
    mark = [time.monotonic()]

    def lap(name: str) -> None:
        now = time.monotonic()
        split[name] = round(now - mark[0], 3)
        mark[0] = now

    split["before_main_s"] = round(mark[0] - t_start, 3)
    import jax

    from chiaswarm_tpu.core.chip_pool import ChipPool
    from chiaswarm_tpu.core.compile_cache import (
        GLOBAL_CACHE,
        enable_persistent_compilation_cache,
    )
    from chiaswarm_tpu.node.minihive import MiniHive
    from chiaswarm_tpu.node.settings import Settings
    from chiaswarm_tpu.node.worker import Worker
    from chiaswarm_tpu.obs.metrics import REGISTRY

    from perfbench import hlo
    from perfbench.attribution import phases_of

    kind = kinds.of(config)
    device = device_facts(int(workload["chips"]), require_tpu)
    lap("imports_and_device_s")
    enable_persistent_compilation_cache()
    compiles = CompileCounter()

    shutil.rmtree(SCRATCH / "root", ignore_errors=True)
    (SCRATCH / "root").mkdir(parents=True, exist_ok=True)
    os.environ["SWARM_TPU_ROOT"] = str(SCRATCH / "root")
    dev0 = jax.devices()[0]
    registry, params, model = kind.build(config, seed, dev0)
    jax.block_until_ready(params)
    lap("weights_on_device_s")
    pool = ChipPool(n_slots=1, devices=[dev0])
    capture = hlo.ProgramCapture() if trace else None
    solo, burst = traffic_mod.warm_jobs(kind, mix, seed, config, model)
    work = traffic_mod.units(mix, kind.UNIT, 4096, seed)

    def make(index: int) -> dict:
        return traffic_mod.make_job(kind, index, work[index], seed, config,
                                    model)

    async def scenario() -> dict:
        hive = MiniHive(**config["hive"])
        uri = await hive.start()
        worker = Worker(settings=Settings(hive_uri=uri,
                                          **config["worker_settings"]),
                        registry=registry, pool=pool)
        run = asyncio.create_task(worker.run())
        win = Window(hive, run)
        # ---- warm-up: set-up, not traffic -------------------------------
        for unit, job in solo:
            t = time.monotonic()
            hive.submit(job)
            got = await win.settle_all({job["id"]}, 1500.0)
            if not got or not got[0]["ok"]:
                raise RuntimeError(f"warm-up job failed: {got}")
            label = traffic_mod.unit_label(unit)
            split[f"warm_solo_{label}_s"] = round(time.monotonic() - t, 3)
            split[f"warm_solo_{label}_phases"] = {
                k: round(v, 3) for k, v in
                (phases_of(got[0]["record"]) or {}).items() if v >= 0.001}
        mark[0] = time.monotonic()
        if burst:
            for _unit, job in burst:
                hive.submit(job)
            got = await win.settle_all({j["id"] for _, j in burst}, 1500.0)
            if len(got) != len(burst) or not all(g["ok"] for g in got):
                raise RuntimeError("warm-up burst failed")
            lap("warm_burst_s")
        split["first_calls_s"] = {
            tag: round(v["sum"], 3) for tag, v in REGISTRY.snapshot()[
                "chiaswarm_compile_seconds"]["values"].items()}
        setup_s = time.monotonic() - t_start
        split["setup_s"] = round(setup_s, 3)
        line = json.dumps({"setup_split": split})
        log(line)
        print(line, file=out, flush=True)
        # ---- the measured window ----------------------------------------
        before = {"compiles": compiles.read(),
                  "registry": REGISTRY.snapshot(),
                  "stepper": dict(worker.health()["stepper"])}
        tracer = None
        if trace:
            from perfbench import trace as trace_mod

            tracer = asyncio.create_task(trace_mod.record(
                SCRATCH / f"trace-{workload['name']}",
                start_after=0.4 * seconds,
                length=min(5.0, 0.25 * seconds)))
        ran = await run_closed(win, hive, int(mix["clients"]), make,
                               seconds)
        after = {"compiles": compiles.read(),
                 "registry": REGISTRY.snapshot(),
                 "stepper": dict(worker.health()["stepper"])}
        traced = await tracer if tracer is not None else None
        stats = dev0.memory_stats() or {}
        ran.update(before=before, after=after, traced=traced,
                   setup_s=setup_s,
                   memory_peak_bytes=stats.get("peak_bytes_in_use"),
                   health=worker.health())
        # ---- drain and drop the program's state -------------------------
        # teardown only: jobs still queued inside the worker are dropped
        # (their leases lapse at the hive), so the graceful stop waits
        # for the jobs in flight and no others
        worker.request_stop()
        deadline = time.monotonic() + 400.0
        while not run.done() and time.monotonic() < deadline:
            while not worker.work_queue.empty():
                worker.work_queue.get_nowait()
                worker.work_queue.task_done()
            await asyncio.sleep(0.02)
        await asyncio.wait_for(run, timeout=1.0)
        await hive.stop()
        return ran

    if capture is not None:
        with capture.patching(*(importlib.import_module(name)
                                for name in kind.PROGRAM_MODULES)):
            ran = asyncio.run(scenario())
    else:
        ran = asyncio.run(scenario())
    del registry, pool
    GLOBAL_CACHE.flush_executables()
    gc.collect()

    # ---- metrics ---------------------------------------------------------
    settled = ran["settled"]
    good = [s for s in settled if s["ok"]]
    window_s = ran["t_close"] - ran["t_open"]
    latencies = [s["t"] - ran["sent"][s["id"]]["due"] for s in good]
    failed = len(settled) - len(good)
    values: dict[str, float] = {"setup_s": ran["setup_s"]}
    if latencies:
        values.update(jobs_per_s=len(good) / window_s,
                      job_p50_s=percentile(latencies, 50))
    compiled_in_window = tuple(
        a - b for a, b in zip(ran["after"]["compiles"],
                              ran["before"]["compiles"]))

    # ---- correct: the plain reference over a sample of the window -------
    verdict = kind.check(params, config, good, ran["sent"], seed=seed,
                         n_jobs=compare_jobs)
    # a program compiled inside the window (a shape that the warm-up
    # missed) voids the run; eager one-off operations
    # (lane bookkeeping at a new width) are counted and reported
    verdict["numbers"]["programs_compiled_in_window"] = {
        "value": float(compiled_in_window[0]), "limit": 0.0}
    log(f"backend compiles inside the window, eager operations "
        f"included: {compiled_in_window[1]}")
    correct = bool(verdict["ok"] and compiled_in_window[0] == 0 and good)

    device_out = dict(device, memory_peak_bytes=ran["memory_peak_bytes"])
    result = {"correct": correct, "attempted": int(ran["attempted"]),
              "failed": int(failed), "metrics": {}, "device": device_out}
    units = {m["name"]: m["unit"] for m in benchmark["end_to_end"]
             + benchmark["per_layer"]}
    if not trace:
        for metric in benchmark["end_to_end"]:
            cells = metric.get("workloads")
            if cells is not None and workload["name"] not in cells:
                continue
            if metric["name"] in values:
                result["metrics"][metric["name"]] = {
                    "value": values[metric["name"]],
                    "unit": metric["unit"]}
    else:
        from perfbench import readers

        context = readers.Context(
            workload=workload, config=config, mix=mix, ran=ran,
            good=good, latencies=latencies, window_s=window_s,
            device=device, capture=capture)
        for metric in benchmark["per_layer"]:
            cells = metric.get("workloads")
            if cells is not None and workload["name"] not in cells:
                continue
            try:
                value = readers.read(metric["name"], context)
            except readers.NoPeaks as exc:
                if require_tpu:
                    raise
                log(f"{metric['name']}: not measured ({exc.args[0]})")
                continue
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": units[metric["name"]]}
        device_out.update(context.device_times())
        breakdown = context.breakdown()
        if breakdown:
            result["breakdown"] = breakdown
    result["compared"] = verdict["numbers"]  # its own key, last
    return result
