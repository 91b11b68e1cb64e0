"""Share of lane row-steps that carried a live row, over the window:
row_steps_active / (active + padded) from the stepper's own counts."""


def read(context):
    active = context.stepper_delta("row_steps_active")
    padded = context.stepper_delta("row_steps_padded")
    if active + padded <= 0:
        return None
    return 100.0 * active / (active + padded)
