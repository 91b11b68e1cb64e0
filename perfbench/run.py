#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Finds the cell in ``BENCHMARK.json``,
its configuration and traffic mix by name under ``perfbench/``, runs it
once (``perfbench/cell.py``) and prints the result object as the last
line of standard output. Needs a TPU: without one (or with fewer chips
than the cell asks for) it exits 2 and prints no result.

``--control N`` (no run of the driver's gives it) runs no window: it
puts the plain reference, one precision below the configuration's, in
the program's place on the cell's first N jobs and prints what
``correct`` makes of it, which has to be false.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# The compile cache is the benchmark's to place: a fixed directory inside
# the checkout, whatever the environment says, so that only the first run
# of a cell in a checkout compiles and two checkouts share nothing. (The
# chip machine's own cache is capped at 192 MiB by
# JAX_COMPILATION_CACHE_MAX_SIZE; the programs of one SDXL cell are
# larger together, so under the cap they evict each other and every run
# compiles again: set-up of 350-530 s where a warm one takes 150.)
import os  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def load_cell(name: str):
    """(benchmark, workload entry, configuration, traffic mix)."""
    from perfbench import traffic

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    workload = cells[name]
    entry = next(c for c in benchmark["configs"]
                 if c["name"] == workload["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    return benchmark, workload, config, traffic.load_mix(workload["traffic"])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, default=0, metavar="N")
    args = parser.parse_args(argv)
    benchmark, workload, config, mix = load_cell(args.workload)

    from perfbench import cell

    if args.control > 0:
        result = cell.run_control(
            workload=workload, config=config, mix=mix, seed=args.seed,
            n_jobs=args.control)
    else:
        result = cell.run_cell(
            workload=workload, config=config, mix=mix, benchmark=benchmark,
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            t_start=T_START)
    for name, pair in result["compared"].items():
        print(f"[perfbench] compared {name} = {pair['value']!r} "
              f"(limit {pair['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
