"""Where one settled job's time went, from the hive's flight record.

A copy of the arithmetic of ``chiaswarm_tpu/obs/flight.py``
(``budget_attribution`` and ``_digest_phase_split``), kept here so that
no later change to the program can move a per-layer metric by editing
it. Input is the JSON view ``MiniHive.flights.get(job_id)`` returns:
hive-clock ``submitted_at``, ``events``, ``settled`` and ``attempts``
(each with its grant time and the worker's span digest).
"""

from __future__ import annotations

PHASES = ("hive_queue", "admission", "lane_wait", "steps", "decode",
          "upload", "retry", "other")


def digest_split(digest) -> dict[str, float]:
    """Worker-side seconds of one span digest: admission (poll receipt
    to lane submit: local queue, format, encode), lane_wait (the lane's
    ``splice_wait_s``), steps, decode."""
    out = {"admission": 0.0, "lane_wait": 0.0, "steps": 0.0, "decode": 0.0}
    if not isinstance(digest, dict):
        return out
    for phase in digest.get("phases") or ():
        if phase.get("name") == "poll":
            out["admission"] += float(phase.get("dur_s") or 0.0)
    for span in digest.get("spans") or ():
        name = span.get("name")
        dur = max(0.0, float(span.get("dur_s") or 0.0))
        if name in ("format", "encode"):
            out["admission"] += dur
        elif name == "step":
            meta = span.get("meta")
            wait = 0.0
            if isinstance(meta, dict):
                try:
                    wait = max(0.0, float(meta.get("splice_wait_s") or 0.0))
                except (TypeError, ValueError):
                    wait = 0.0
            wait = min(wait, dur)
            out["lane_wait"] += wait
            out["steps"] += dur - wait
        elif name == "decode":
            out["decode"] += dur
    return out


def phases_of(record: dict) -> dict[str, float] | None:
    """Seconds per phase of one settled record; None if it never
    settled. ``other`` is what no named phase explains."""
    settled = record.get("settled")
    submitted = record.get("submitted_at")
    if settled is None or submitted is None:
        return None
    t_settle = float(settled["t"])
    final = int(settled.get("attempt") or 0)
    attempts = {int(a["attempt"]): a for a in record.get("attempts") or ()}
    hive_queue = retry = 0.0
    last_enqueue: float | None = float(submitted)
    open_grant = None
    for event in record.get("events") or ():
        kind, t = event.get("event"), float(event.get("t") or 0.0)
        if kind == "grant":
            if last_enqueue is not None:
                hive_queue += max(0.0, t - last_enqueue)
                last_enqueue = None
            open_grant = (int(event.get("attempt") or 0), t)
        elif kind in ("redispatched", "redelivered", "lease_expired"):
            if open_grant is not None:
                if open_grant[0] != final:
                    retry += max(0.0, t - open_grant[1])
                open_grant = None
            if kind == "lease_expired" or last_enqueue is None:
                last_enqueue = t
    attempt = attempts.get(final) or {}
    digest = attempt.get("digest")
    split = digest_split(digest)
    upload = 0.0
    if isinstance(digest, dict) and attempt.get("t") is not None:
        upload = max(0.0, (t_settle - float(attempt["t"]))
                     - float(digest.get("duration_s") or 0.0))
    total = max(0.0, t_settle - float(submitted))
    phases = {"hive_queue": hive_queue, "admission": split["admission"],
              "lane_wait": split["lane_wait"], "steps": split["steps"],
              "decode": split["decode"], "upload": upload, "retry": retry}
    phases["other"] = max(0.0, total - sum(phases.values()))
    return phases
