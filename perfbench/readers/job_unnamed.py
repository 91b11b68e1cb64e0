"""Median over the window's jobs of what no named leaf covers: (settle -
submitted, hive clock) less the hive queue and the upload (frozen
attribution, perfbench/attribution.py), the ``poll`` phase and every
leaf span of ``leaves`` in the worker's digest. None where no job's
digest holds the lane's own spans (``needs``): there the split is the
frozen attribution's, not this one."""
import statistics

from perfbench import spans as digests
from perfbench.attribution import phases_of


def read(context, leaves, needs):
    if not digests.on_chip(context):
        return None
    values = []
    for settled in context.good:
        record = settled["record"]
        digest = digests.final_digest(record)
        frozen = phases_of(record)
        if not digest or not frozen:
            continue
        if digests.span_seconds(digest, needs) is None:
            continue
        total = float(record["settled"]["t"]) - float(record["submitted_at"])
        named = (frozen["hive_queue"] + frozen["upload"]
                 + digests.phase_seconds(digest, "poll")
                 + (digests.span_seconds(digest, leaves) or 0.0))
        values.append(total - named)
    return statistics.median(values) if values else None
