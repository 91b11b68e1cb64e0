#!/usr/bin/env python3
"""Reconstruct one job's cross-worker lifecycle from its flight record.

swarmsight CLI (ISSUE 13): fetches ``GET /api/flight/<job_id>`` from a
MiniHive-protocol hive (or reads a saved record from a file) and renders
the stitched story — submit, every grant(attempt, worker), checkpoint
markers, shed/redispatch/redelivery/salvage, the exactly-once settle —
with each attempt's worker span digest aligned onto the hive clock at
its grant anchor (the residual against the settle anchor prints as
``clock_skew_s``). The heavy lifting lives in
``chiaswarm_tpu/obs/flight.py`` (stdlib-only; this tool runs without
jax); this is the thin CLI.

Formats:

- ``tree`` (default): nested events + per-attempt span trees + the
  deadline-budget attribution table.
- ``timeline``: one merged hive-clock timeline interleaving hive events
  and worker spans across workers.
- ``perfetto``: chrome-tracing JSON spanning workers (pid 0 = hive
  events, one pid per worker, one tid per attempt) — load at
  https://ui.perfetto.dev.

Examples::

    python tools/job_flight.py load-7 --hive http://127.0.0.1:8555
    python tools/job_flight.py --file flight.json --format timeline
    python tools/job_flight.py lane-0 --hive $HIVE --format perfetto \
        --out lane0.trace.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from chiaswarm_tpu.obs.flight import (  # noqa: E402
    flight_to_chrome,
    render_timeline,
    render_tree,
)


def fetch_record(hive: str, job_id: str) -> dict:
    url = f"{hive.rstrip('/')}/api/flight/{job_id}"
    try:
        with urllib.request.urlopen(url, timeout=30) as response:
            return json.loads(response.read().decode("utf-8"))
    except urllib.error.HTTPError as exc:
        if exc.code == 404:
            raise SystemExit(
                f"no flight record for job {job_id!r} at {hive} "
                f"(evicted, or the job was never submitted there)")
        raise SystemExit(f"flight fetch failed: HTTP {exc.code} ({url})")
    except urllib.error.URLError as exc:
        raise SystemExit(f"flight fetch failed: {exc.reason} ({url})")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="job_flight",
        description="render one job's cross-worker flight record")
    parser.add_argument("job_id", nargs="?",
                        help="job id to fetch (with --hive)")
    parser.add_argument("--hive",
                        help="hive base URI serving /api/flight/<id>")
    parser.add_argument("--file",
                        help="read a saved flight-record JSON instead "
                             "of fetching")
    parser.add_argument("--format", default="tree",
                        choices=("tree", "timeline", "perfetto"))
    parser.add_argument("--out",
                        help="write output here instead of stdout")
    args = parser.parse_args(argv)

    if args.file:
        with open(args.file, encoding="utf-8") as handle:
            record = json.load(handle)
    elif args.hive and args.job_id:
        record = fetch_record(args.hive, args.job_id)
    else:
        parser.error("need either --file RECORD.json, or JOB_ID --hive "
                     "URI")
        return 2  # unreachable; parser.error exits

    if args.format == "perfetto":
        body = json.dumps(flight_to_chrome(record))
    elif args.format == "timeline":
        body = render_timeline(record)
    else:
        body = render_tree(record)

    if args.out:
        with open(args.out, "w", encoding="utf-8") as handle:
            handle.write(body + "\n")
        print(f"wrote {args.format} for job "
              f"{record.get('job_id')!r} to {args.out}")
    else:
        print(body)
    return 0


if __name__ == "__main__":
    sys.exit(main())
