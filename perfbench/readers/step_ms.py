"""Mean lane step over the window: chiaswarm_stepper_step_seconds, sum
over count (host wall of a dispatch under the depth-2 pipeline gate,
which in steady state is the device's step time)."""

FAMILY = "chiaswarm_stepper_step_seconds"


def read(context):
    count = context.counter_delta(FAMILY, "count")
    if count <= 0:
        return None
    return 1e3 * context.counter_delta(FAMILY, "sum") / count
