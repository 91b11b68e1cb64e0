"""Time-weighted share of the roofline of one kind of operation in the
traced window: device time from the trace, operations and bytes from the
captured programs' HLO (perfbench/hlo.py)."""
from perfbench import trace


def read(context, kinds):
    form = context.form
    if not form or not context.costs:
        return None
    peaks = context.peaks
    found = trace.kernel_roofline(
        form, context.costs, tuple(kinds), peaks["bf16_tflops"] * 1e12,
        peaks["hbm_gbps"] * 1e9)
    return None if found is None else 100.0 * found["share"]
