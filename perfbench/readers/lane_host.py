"""Host milliseconds per lane boundary outside the step: the window
delta of chiaswarm_stepper_boundary_seconds summed over ``parts``, over
the window delta of chiaswarm_stepper_step_seconds' count. None where the
program has no such family or no step ran."""

from perfbench import spans as digests

BOUNDARY = "chiaswarm_stepper_boundary_seconds"
STEPS = "chiaswarm_stepper_step_seconds"


def read(context, parts):
    if not digests.on_chip(context):
        return None

    def seconds(snapshot):
        values = snapshot.get(BOUNDARY, {}).get("values")
        if not values:
            return None
        return sum(v["sum"] for part, v in values.items() if part in parts)

    before = seconds(context.ran["before"]["registry"])
    after = seconds(context.ran["after"]["registry"])
    steps = context.counter_delta(STEPS, "count")
    if after is None or steps <= 0:
        return None
    return 1e3 * (after - (before or 0.0)) / steps
