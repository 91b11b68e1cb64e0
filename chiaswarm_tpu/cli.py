"""``swarm-tpu`` — the node operator CLI.

One entry point over the reference's three module scripts
(``python -m swarm.initialize`` / ``swarm.worker`` / ``swarm.test``,
SURVEY.md §1 L6):

    swarm-tpu init [--reset --silent --warm-compile]   configure + prefetch
    swarm-tpu worker                                   serve the swarm
    swarm-tpu smoke [--workflow X | --all]             hermetic smoke jobs
    swarm-tpu info                                     device/mesh report
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys


def cmd_info(_args) -> int:
    import jax

    from chiaswarm_tpu import WORKER_VERSION
    from chiaswarm_tpu.core.chip_pool import ChipPool

    pool = ChipPool(n_slots=1)
    print(json.dumps({
        "worker_version": WORKER_VERSION,
        "backend": jax.default_backend(),
        "devices": [str(d) for d in jax.devices()],
        "slots": pool.descriptor(),
    }, indent=2))
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="swarm-tpu", description=__doc__)
    sub = parser.add_subparsers(dest="command", required=True)
    sub.add_parser("init", add_help=False)
    sub.add_parser("worker")
    sub.add_parser("smoke", add_help=False)
    sub.add_parser("info")

    args, rest = parser.parse_known_args(argv)

    if args.command == "init":
        from chiaswarm_tpu.node.initialize import init

        return asyncio.run(init(rest))
    if args.command == "worker":
        from chiaswarm_tpu.node.worker import run_worker

        # the guard's restart rung surfaces as a distinct exit code
        # (serving/guard.py GUARD_RESTART_EXIT_CODE) so supervisors
        # restart-on-73 instead of paging a crash
        return asyncio.run(run_worker())
    if args.command == "smoke":
        from chiaswarm_tpu.node.smoke import main as smoke_main

        return smoke_main(rest)
    if args.command == "info":
        return cmd_info(args)
    return 2


if __name__ == "__main__":
    sys.exit(main())
