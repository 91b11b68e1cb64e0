"""Median over the window's jobs of the seconds one or more named spans
of the worker's span digest took (summed within a job). None where no
job's digest holds any of them (a program without those spans)."""
import statistics

from perfbench import spans as digests


def read(context, spans):
    if not digests.on_chip(context):
        return None
    values = []
    for settled in context.good:
        digest = digests.final_digest(settled["record"])
        seconds = digests.span_seconds(digest, spans) if digest else None
        if seconds is not None:
            values.append(seconds)
    return statistics.median(values) if values else None
