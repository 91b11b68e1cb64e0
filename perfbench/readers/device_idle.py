"""1 - (union of device-operation intervals) / traced window."""
from perfbench import trace


def read(context):
    form = context.form
    share = trace.idle_share(form) if form else None
    return None if share is None else 100.0 * share
