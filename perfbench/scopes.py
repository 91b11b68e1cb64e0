"""Device time by scope inside a program (ISSUE 37): the join of the
profiler trace to the scope vocabulary that the program writes into its
operations' ``op_name`` metadata (``chiaswarm_tpu/obs/trace.py::scope``).

The compiled HLO carries each operation's ``op_name``, a path such as
``jit(text_decode)/while/body/closed_call/experts/dot_general`` (XLA
joins two with ``;`` where it merges operations). An operation carries
the vocabulary's names that are components of its path, each once. A
device event (``trace.load``'s "XLA Ops" line) belongs to the execution
of the program whose "XLA Modules" event (``programs.load``) holds it,
and is joined to that program's operation of its name (and result shape,
where two captured executables of one program share a name). A scope's
time is the device time of the events that carry it, inclusive as a
span's duration includes its children; container operations stay out,
as in ``trace.op_totals``.

The vocabulary is kept here, as ``hlo.py`` keeps its costing, so that
what a metric reads cannot change with the program; ``tests/
test_scopes.py`` holds it to the program's. A program built before the
scopes existed carries none of them, and every reading of it is None.
"""

from __future__ import annotations

import bisect
import re

from perfbench import hlo, trace
from perfbench.readers import prefill_attention_roofline, program_whole

SCOPES = ("attention", "recurrence", "router", "experts", "shared_expert",
          "dense_mlp", "head", "sample", "resnet", "transformer", "sampler")

_MODULE_RE = re.compile(r"^\s*HloModule\s+([\w.-]+)")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def scopes_of(path: str) -> frozenset:
    """The vocabulary's names among the components of an ``op_name``
    (``;`` between merged paths, ``/`` between components)."""
    return frozenset(part for merged in path.split(";")
                     for part in merged.split("/") if part in SCOPES)


def program_map(text: str) -> tuple[str, dict]:
    """(module name, {operation name: {result shape: scopes}}) of one
    program's HLO text, every computation's instructions included (the
    bodies of loops run as events of their own)."""
    first = _MODULE_RE.match(text)
    ops: dict[str, dict] = {}
    for _computation, line in hlo.iter_instruction_lines(text):
        definition = _DEF_RE.match(line)
        if not definition:
            continue
        path = _OP_NAME_RE.search(line)
        ops.setdefault(definition.group(1), {})[hlo.result_shape(line)] = \
            scopes_of(path.group(1)) if path else frozenset()
    return (first.group(1) if first else ""), ops


def program_maps(texts) -> dict[str, dict]:
    """module name -> its operations' scopes, over every captured
    executable (two executables of one program, at two shapes, merge)."""
    maps: dict[str, dict] = {}
    for text in texts:
        module, ops = program_map(text)
        merged = maps.setdefault(module, {})
        for name, by_shape in ops.items():
            merged.setdefault(name, {}).update(by_shape)
    return maps


def event_scopes(ops: dict, event_name: str) -> frozenset | None:
    """The scopes of the operation a device event ran; None where the
    program has no operation of that name (and shape)."""
    by_shape = ops.get(trace.op_name(event_name))
    if not by_shape:
        return None
    if len(by_shape) == 1:
        return next(iter(by_shape.values()))
    return by_shape.get(hlo.result_shape(event_name))


def carries(maps: dict, program: str, scope: str) -> bool:
    """Whether any operation of ``program`` carries ``scope``."""
    return any(scope in scopes
               for by_shape in maps.get(program, {}).values()
               for scopes in by_shape.values())


def split(events: list, ops: dict) -> dict:
    """Device ms of ``events`` by scope (an event under two scopes counts
    in each), plus ``"all"`` (every operation, containers out) and
    ``"scoped"`` (those under any scope)."""
    out = {"all": 0.0, "scoped": 0.0}
    for name, _start, dur in events:
        if trace.is_container(trace.op_name(name)):
            continue
        ms = dur * 1e-6
        out["all"] += ms
        scopes = event_scopes(ops, name) or frozenset()
        if scopes:
            out["scoped"] += ms
        for scope in scopes:
            out[scope] = out.get(scope, 0.0) + ms
    return out


def splits(device: list, maps: dict, modules: list, program: str,
           run: int | None = None) -> list[dict]:
    """One ``split`` of the events inside each whole execution of
    ``program`` (with ``run``, each whole run of ``run`` executions: one
    job's prefill chunks, by ``prefill_attention_roofline``'s rule)."""
    device = sorted(device, key=lambda event: event[1])
    starts = [start for _, start, _ in device]
    intervals = (
        prefill_attention_roofline.whole_runs(modules, program, run) if run
        else program_whole.whole_executions(modules, program))
    return [split([event for event in device[bisect.bisect_left(starts, lo):
                                             bisect.bisect_left(starts, hi)]
                   if event[1] + event[2] <= hi], maps.get(program, {}))
            for lo, hi in intervals]
