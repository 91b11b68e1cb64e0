"""The whole step's share of the chip's peak: the operations the jobs
settled in the window needed (the kind's ``job_flops``) over window
seconds times the published bf16 peak."""


def read(context):
    if not context.good or context.window_s <= 0:
        return None
    needed = sum(
        context.kind.job_flops(context.config,
                               context.ran["sent"][s["id"]]["job"])
        for s in context.good)
    peak = context.peaks["bf16_tflops"] * 1e12
    return 100.0 * needed / (context.window_s * peak)
