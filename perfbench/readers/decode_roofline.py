"""Share of its roofline one job's decode reaches: the least time the
chip could take for what the decode has to do whatever implements it,
over the time a whole execution of ``program`` took. The least time is
the larger of operations over the bf16 peak and bytes over the memory
bandwidth, each summed over the WHOLE decode first (the kind's
``decode_flops`` at the query-key pairs the program's counter
``attention_pairs`` counted, ``decode_bytes`` at the held experts its
counter ``experts_hit`` counted). The larger of two sums is at most the
sum of each operation's own larger bound, so the share reads low where
the work is mixed, and cannot pass 100%. The time is the median of the
executions the trace cut at neither end (``program_whole``). None
without a trace, off the chip, for a kind without the two functions, or
where the program has no such counters or no whole execution."""
from perfbench.readers import counter_ratio, program_whole


def read(context, program, experts_hit, attention_pairs):
    count_bytes = getattr(context.kind, "decode_bytes", None)
    count_flops = getattr(context.kind, "decode_flops", None)
    if count_bytes is None or count_flops is None or not context.good:
        return None
    hit = counter_ratio.delta(context, experts_hit["family"],
                              experts_hit["labels"])
    pairs = counter_ratio.delta(context, attention_pairs["family"],
                                attention_pairs["labels"])
    whole_ms = program_whole.read(context, program)
    if hit is None or pairs is None or not whole_ms:
        return None
    jobs = [context.ran["sent"][s["id"]]["job"] for s in context.good]
    n = len(jobs)
    flops = sum(count_flops(context.config, job, pairs / n)
                for job in jobs) / n
    moved = sum(count_bytes(context.config, job, hit / n)
                for job in jobs) / n
    peaks = context.peaks
    least_s = max(flops / (peaks["bf16_tflops"] * 1e12),
                  moved / (peaks["hbm_gbps"] * 1e9))
    return 100.0 * least_s / (whole_ms * 1e-3)
