"""Share of the memory-bandwidth roofline one job's decode reaches: the
bytes the decode of a job has to move whatever implements it (the kind's
``decode_bytes``: weights outside the experts once a step, the held
experts the program's counter says were hit, each row's recurrent state
read and written, the prompt's latents once and each row's suffix) over
the mean device time of ``program`` x the chip's published bandwidth.
None without a trace, off the chip, for a kind without ``decode_bytes``,
or where the program has no such counter or never ran."""
from perfbench.readers import counter_ratio, program_device


def read(context, program, experts_hit):
    count_bytes = getattr(context.kind, "decode_bytes", None)
    if count_bytes is None or not context.good:
        return None
    hit = counter_ratio.delta(context, experts_hit, "")
    mean_ms = program_device.read(context, program)
    if hit is None or not mean_ms:
        return None
    jobs = [context.ran["sent"][s["id"]]["job"] for s in context.good]
    needed = sum(count_bytes(context.config, job, hit / len(jobs))
                 for job in jobs) / len(jobs)
    return 100.0 * needed / (mean_ms * 1e-3
                             * context.peaks["hbm_gbps"] * 1e9)
