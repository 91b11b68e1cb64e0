"""Median device duration of the WHOLE executions of one jitted program
in the traced window (perfbench/programs.py: the chip plane's "XLA
Modules" line). A capture that opens or closes inside an execution
records the part it saw, as an event that begins at the first or ends at
the last nanosecond of the line; such an event is left out, so the
number is a property of the program and not of where the window fell in
the job (PERF.md 5b). A whole event that happens to be the line's first
or last is left out with them. None without a trace, off the chip, or
where no whole execution of the program is left."""
import statistics

from perfbench import programs
from perfbench import spans as digests


def whole_ms(form: dict, program: str) -> list[float]:
    """Milliseconds of each execution of ``program`` that the trace cut
    at neither end."""
    modules = form["modules"]
    if not modules:
        return []
    first = min(start for _, start, _ in modules)
    last = max(start + dur for _, start, dur in modules)
    return [dur * 1e-6 for name, start, dur in modules
            if programs.program_name(name) == program
            and start > first and start + dur < last]


def read(context, program):
    traced = context.ran.get("traced")
    if not traced or not digests.on_chip(context):
        return None
    try:
        form = programs.load(traced["dir"], traced["window_s"])
    except (FileNotFoundError, ImportError):
        return None
    whole = whole_ms(form, program)
    return statistics.median(whole) if whole else None
