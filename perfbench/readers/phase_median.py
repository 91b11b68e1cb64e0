"""Median over the window's jobs of one phase of the hive's flight
record (perfbench/attribution.py)."""
import statistics

from perfbench.attribution import phases_of


def read(context, phase: str):
    values = [p[phase] for p in (phases_of(s["record"])
                                 for s in context.good) if p]
    return statistics.median(values) if values else None
