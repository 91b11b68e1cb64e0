"""The whole step's share of the chip's peak: the operations the jobs
settled in the window needed (perfbench/flops.py) over window seconds
times the published bf16 peak."""
from perfbench import flops


def read(context):
    serving = context.config["serving"]
    if not context.good or context.window_s <= 0:
        return None
    needed = sum(
        flops.job(context.config,
                  context.ran["sent"][s["id"]]["job"]["num_inference_steps"],
                  serving["height"], serving["width"])
        for s in context.good)
    peak = context.peaks["bf16_tflops"] * 1e12
    return 100.0 * needed / (context.window_s * peak)
