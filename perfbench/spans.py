"""Reading a settled job's span digest (the worker's half of the hive's
flight record) by span name, for the readers that ISSUE 26 added.

The digest is ``record["attempts"][final]["digest"]``: ``phases`` (poll /
execute / upload) and ``spans`` (everything below them, flat, each with
``name``, ``phase``, ``t0_s``, ``dur_s``). A program that lacks a span
gives None, never 0.
"""

from __future__ import annotations


def on_chip(context) -> bool:
    """The span and counter readers added after the first benchmark
    report on a TPU only: ``tests/bench/test_bench_run.py`` (frozen)
    holds a traced CPU run to the six host metrics of the first
    benchmark, and a tiny model's seconds under a real cell's metric
    name would say nothing."""
    return (context.device or {}).get("platform") == "tpu"


def final_digest(record: dict) -> dict | None:
    """The span digest of the attempt that settled the job."""
    settled = record.get("settled")
    if not settled:
        return None
    final = int(settled.get("attempt") or 0)
    for attempt in record.get("attempts") or ():
        if int(attempt.get("attempt") or 0) == final:
            digest = attempt.get("digest")
            return digest if isinstance(digest, dict) else None
    return None


def span_seconds(digest: dict, names) -> float | None:
    """Summed ``dur_s`` of the digest's spans named in ``names``; None
    if it holds none of them."""
    found = [max(0.0, float(span.get("dur_s") or 0.0))
             for span in digest.get("spans") or ()
             if span.get("name") in names]
    return sum(found) if found else None


def phase_seconds(digest: dict, name: str) -> float:
    return sum(max(0.0, float(phase.get("dur_s") or 0.0))
               for phase in digest.get("phases") or ()
               if phase.get("name") == name)
