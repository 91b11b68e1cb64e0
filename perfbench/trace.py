"""The profiler trace: recording a few seconds of the steady window,
and the reduction from its events to numbers.

The reduction works on a plain form, so that it can be checked on a small
recorded trace kept with the benchmark (``fixtures/``):

    {"window_s": float,
     "device": [[name, start_ns, dur_ns], ...],   # device operations
     "host":   [[name, start_ns, dur_ns], ...]}   # host spans, same clock

``device`` holds the events of the chip's "XLA Ops" line (one chip: the
first TPU plane). Container operations (while / conditional / call) span
their bodies, whose operations are on the same line, and are left out.
``host`` holds the host threads' spans (``jax.profiler.TraceAnnotation``
such as the program's ``swarm.lane.step``, and the runtime's own).
"""

from __future__ import annotations

import asyncio
import glob
import shutil
import time
from pathlib import Path

CONTAINERS = ("while", "conditional", "call")


def is_container(name: str) -> bool:
    return name.split(".")[0] in CONTAINERS


async def record(directory: Path, *, start_after: float, length: float):
    """Trace ``length`` seconds, ``start_after`` seconds from now. Start
    and stop run off the event loop (stopping serializes the trace)."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    directory.mkdir(parents=True, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    await asyncio.sleep(start_after)
    await asyncio.to_thread(jax.profiler.start_trace, str(directory),
                            profiler_options=options)
    t0 = time.monotonic()
    await asyncio.sleep(length)
    t1 = time.monotonic()
    await asyncio.to_thread(jax.profiler.stop_trace)
    return {"dir": str(directory), "window_s": t1 - t0}


def load(directory: str, window_s: float) -> dict:
    """The plain form of the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{directory}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane under {directory}")
    data = ProfileData.from_file(paths[-1])
    device: list[list] = []
    host: list[list] = []
    tpu_planes = sorted((p for p in data.planes
                         if p.name.startswith("/device:TPU")),
                        key=lambda p: p.name)
    if tpu_planes:
        for line in tpu_planes[0].lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                device.append([event.name[:200], int(event.start_ns),
                               int(event.duration_ns)])
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.duration_ns > 0:
                    host.append([event.name[:200], int(event.start_ns),
                                 int(event.duration_ns)])
    return {"window_s": float(window_s), "device": device, "host": host}


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].lstrip("%").strip()


def busy_intervals(form: dict) -> list[tuple[int, int]]:
    """Merged [start, end) intervals in which an operation ran."""
    spans = sorted((s, s + d) for name, s, d in form["device"]
                   if d > 0 and not is_container(op_name(name)))
    merged: list[list[int]] = []
    for start, end in spans:
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_seconds(form: dict) -> float:
    return sum(b - a for a, b in busy_intervals(form)) * 1e-9


def idle_share(form: dict) -> float | None:
    """1 - busy / window; None without device events."""
    if not form["device"] or form["window_s"] <= 0:
        return None
    return max(0.0, 1.0 - busy_seconds(form) / form["window_s"])


def op_totals(form: dict, by_shape: bool = False) -> dict:
    """op name -> {"seconds", "count", "signature"}, containers out.
    ``by_shape`` keys by (name, result shape) instead: the lane programs
    of two widths number their fusions alike, and only the shape in the
    event's text tells their operations apart."""
    from perfbench.hlo import result_shape

    totals: dict = {}
    for name, _start, dur in form["device"]:
        op = op_name(name)
        if is_container(op):
            continue
        key = (op, result_shape(name)) if by_shape else op
        entry = totals.setdefault(
            key, {"seconds": 0.0, "count": 0, "signature": name})
        entry["seconds"] += dur * 1e-9
        entry["count"] += 1
    return totals


def idle_gaps(form: dict, top: int = 10) -> list[list]:
    """The longest gaps between busy intervals, each named by the
    innermost host span that covers the gap's middle (``unattributed``
    if none does), summed by name: [[name, seconds], ...]."""
    intervals = busy_intervals(form)
    gaps = [(b_start - a_end, a_end, b_start)
            for (_a, a_end), (b_start, _b) in zip(intervals, intervals[1:])
            if b_start > a_end]
    gaps.sort(reverse=True)
    host = sorted(form["host"], key=lambda e: e[2])  # shortest first
    by_name: dict[str, float] = {}
    for length, start, end in gaps[:200]:
        middle = (start + end) // 2
        name = next((n for n, s, d in host if s <= middle < s + d),
                    "unattributed")
        by_name[name] = by_name.get(name, 0.0) + length * 1e-9
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return [[name, seconds] for name, seconds in ranked[:top]]


def kernel_roofline(form: dict, costs: dict, kinds: tuple,
                    peak_flops: float, peak_bytes: float) -> dict | None:
    """Time-weighted share of the roofline over the operations whose
    static cost is of one of ``kinds``: sum of max(flops/peak,
    bytes/bandwidth) over sum of device time. ``costs`` is keyed by
    (operation name, result shape). None if no such operation ran, or if
    one ran whose cost is None (a flash call holding no attention the
    configuration states). Also says which side binds most of the bound."""
    bound = seconds = by_flops = 0.0
    for key, total in op_totals(form, by_shape=True).items():
        cost = costs.get(key)
        if not cost or cost["kind"] not in kinds:
            continue
        if cost["flops"] is None:
            return None
        t_c = cost["flops"] * total["count"] / peak_flops
        t_b = cost["bytes"] * total["count"] / peak_bytes
        bound += max(t_c, t_b)
        by_flops += t_c if t_c >= t_b else 0.0
        seconds += total["seconds"]
    if seconds <= 0:
        return None
    return {"share": bound / seconds, "seconds": seconds,
            "bound": "flops" if by_flops >= bound / 2 else "hbm"}
