"""Share of its roofline one Mosaic kernel reaches over one job's calls:
the least time the chip could take for what the kernel has to do a job
(the larger of operations over the bf16 peak and bytes over the memory
bandwidth, from the two functions of the kind that ``flops`` and
``bytes`` name, each of ``(config, job)``), over the device time of the
operations named ``kernel`` in one job's executions of ``program``.

The kernel's work depends on traced operands (a chunk's position, the
prompt's length), so no one call prices it; a job's calls together do.
They lie in a run of closely following executions of ``program``, as
many as the kind's function that ``executions`` names says a job makes
(``prefill_attention_roofline``'s rule, with ``prefill_chunks``), or,
where ``executions`` is null, in ONE execution (a job's whole decode),
that the trace cut at neither end. The time is the
kernel's, summed over each such run and averaged over the runs. The
operations are what the algorithm needs (a windowed layer's at the
VISIBLE pairs), so what the kernel computes beside them (masked parts of
blocks on an edge) only lowers the share, and it cannot pass 100.
``counter`` names a series of the program's that the kernel's path feeds
(``{"family", "labels"}``): None where the run's program has no such
series (a program without this path), without a trace, off the chip, for
a kind without the functions, or where the trace holds no whole run.
"""
from perfbench import programs
from perfbench import spans as digests
from perfbench.readers import counter_ratio
from perfbench.readers.prefill_attention_roofline import (
    kernel_seconds,
    whole_runs,
)


def whole_executions(modules: list, program: str) -> list[tuple]:
    """[start_ns, end_ns) of each execution of ``program`` that the
    trace cut at neither end (``program_whole``'s rule)."""
    if not modules:
        return []
    first = min(start for _, start, _ in modules)
    last = max(start + dur for _, start, dur in modules)
    return [(start, start + dur) for name, start, dur in modules
            if programs.program_name(name) == program
            and start > first and start + dur < last]


def read(context, program, kernel, executions, flops, bytes, counter):
    kind = context.kind
    count_flops = getattr(kind, flops, None)
    count_bytes = getattr(kind, bytes, None)
    per_job = getattr(kind, executions, None) if executions else None
    traced = context.ran.get("traced")
    if count_flops is None or count_bytes is None \
            or (executions and per_job is None) or not context.good \
            or not traced or not digests.on_chip(context):
        return None
    moved = counter_ratio.delta(context, counter["family"],
                                counter["labels"])
    if not moved:
        return None
    try:
        modules = programs.load(traced["dir"], traced["window_s"])["modules"]
    except (FileNotFoundError, ImportError):
        return None
    jobs = [context.ran["sent"][s["id"]]["job"] for s in context.good]
    if per_job is None:
        runs = whole_executions(modules, program)
    else:
        lengths = {per_job(context.config, job) for job in jobs}
        if len(lengths) != 1:
            return None
        runs = whole_runs(modules, program, lengths.pop())
    seconds = kernel_seconds(context.form["device"], kernel, runs)
    if not runs or seconds <= 0:
        return None
    n = len(jobs)
    needed = sum(count_flops(context.config, job) for job in jobs) / n
    moved = sum(count_bytes(context.config, job) for job in jobs) / n
    peaks = context.peaks
    least_s = max(needed / (peaks["bf16_tflops"] * 1e12),
                  moved / (peaks["hbm_gbps"] * 1e9))
    return 100.0 * least_s / (seconds / len(runs))
