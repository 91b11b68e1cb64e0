"""Share of its roofline the causal prefill kernel reaches over one
job's prefill: the least time the chip could take for the attention of
the prompt (the larger of operations over the bf16 peak and bytes over
the memory bandwidth, from the kind's ``prefill_attention_flops`` at the
query-key pairs the program's counter ``attention_pairs`` counted, and
its ``prefill_attention_bytes``), over the device time of the operations
named ``kernel`` in one job's executions of ``program``.

The kernel's work depends on where a chunk lies in the prompt, so no one
execution prices it; a whole prefill does. One is a run of executions of
``program`` that follow one another closely (the gap to the next is
shorter than either of the two: a job's chunks run back to back with at
most small helper programs between, and the decode follows), of as many
executions as the kind's ``prefill_chunks`` says the job has, that the
trace cut at neither end (``program_whole``'s rule: a run that begins at
the line's first or ends at its last nanosecond is left out). The time
is the kernel's, summed over each such run and averaged over the runs;
the pairs are the counter's over the window's jobs, averaged likewise.
The kernel also scores the masked half of the blocks on the diagonal,
which the pairs leave out, so the share cannot pass 100. None without a
trace, off the chip, for a kind without the three functions, where the
program has no such counter, or where the trace holds no whole prefill.
"""
from perfbench import programs, trace
from perfbench import spans as digests
from perfbench.readers import counter_ratio


def whole_runs(modules: list, program: str, length: int) -> list[tuple]:
    """[start_ns, end_ns) of each run of exactly ``length`` closely
    following executions of ``program`` that the trace cut at neither
    end."""
    if not modules:
        return []
    first = min(start for _, start, _ in modules)
    last = max(start + dur for _, start, dur in modules)
    runs: list[list[tuple]] = []
    for name, start, dur in sorted(modules, key=lambda m: m[1]):
        if programs.program_name(name) != program:
            continue
        if runs:
            before, before_dur = runs[-1][-1]
            if start - (before + before_dur) < min(before_dur, dur):
                runs[-1].append((start, dur))
                continue
        runs.append([(start, dur)])
    return [(run[0][0], run[-1][0] + run[-1][1]) for run in runs
            if len(run) == length and run[0][0] > first
            and run[-1][0] + run[-1][1] < last]


def kernel_seconds(device: list, kernel: str, runs: list[tuple]) -> float:
    """Device seconds of the operations named ``kernel`` (``kernel``,
    ``kernel.1``, ...) that lie inside ``runs``."""
    total = 0
    for name, start, dur in device:
        if trace.op_name(name).split(".")[0] == kernel and any(
                a <= start and start + dur <= b for a, b in runs):
            total += dur
    return total * 1e-9


def read(context, program, kernel, attention_pairs):
    kind = context.kind
    counts = [getattr(kind, name, None) for name in (
        "prefill_chunks", "prefill_attention_flops",
        "prefill_attention_bytes")]
    traced = context.ran.get("traced")
    if None in counts or not context.good or not traced \
            or not digests.on_chip(context):
        return None
    chunks, count_flops, count_bytes = counts
    pairs = counter_ratio.delta(context, attention_pairs["family"],
                                attention_pairs["labels"])
    if pairs is None:
        return None
    try:
        modules = programs.load(traced["dir"], traced["window_s"])["modules"]
    except (FileNotFoundError, ImportError):
        return None
    jobs = [context.ran["sent"][s["id"]]["job"] for s in context.good]
    n = len(jobs)
    lengths = {chunks(context.config, job) for job in jobs}
    if len(lengths) != 1:
        return None
    runs = whole_runs(modules, program, lengths.pop())
    seconds = kernel_seconds(context.form["device"], kernel, runs)
    if not runs or seconds <= 0:
        return None
    flops = sum(count_flops(context.config, job, pairs / n)
                for job in jobs) / n
    moved = sum(count_bytes(context.config, job) for job in jobs) / n
    peaks = context.peaks
    least_s = max(flops / (peaks["bf16_tflops"] * 1e12),
                  moved / (peaks["hbm_gbps"] * 1e9))
    return 100.0 * least_s / (seconds / len(runs))
