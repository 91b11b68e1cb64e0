"""Mean device duration of one execution of one jitted program in the
traced window (perfbench/programs.py: the chip plane's "XLA Modules"
line). None without a trace, or where no program of that name ran (a
program that still calls every executable ``jit_fn``)."""
from perfbench import programs
from perfbench import spans as digests


def read(context, program):
    traced = context.ran.get("traced")
    if not traced or not digests.on_chip(context):
        return None
    try:
        form = programs.load(traced["dir"], traced["window_s"])
    except (FileNotFoundError, ImportError):
        return None
    return programs.mean_ms(form, program)
