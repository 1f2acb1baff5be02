#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmark/run.py --workload <config>.<traffic> --seed <n>
                            --seconds <s> --trace <0|1>

Builds the chain from the seed (in a child process), starts the source
peers there, boots a real `Node` on the chip, lets it warm, measures for
`--seconds`, checks, and prints one JSON object as the last line of
stdout.  It fails, and does not fall back, when jax finds no TPU.  See
`benchmark/README.md`.
"""

import time

T_START = time.monotonic()       # set-up is counted from here

import argparse                  # noqa: E402
import json                      # noqa: E402
import os                        # noqa: E402
import sys                       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the whole run stays inspectable: no span may fall off the ring.  A run
# of testnet-4v writes 13 records a height from boot, 280,000 in a
# checkout's first run (my chip runs, PR 26): 4 Mi slots (32 MB of
# pointers) hold a change 2.5x the parent several times over
os.environ.setdefault("TM_FLIGHT_RECORDER_CAP", "4194304")


def execute(workload: str, seed: int, seconds: float, trace: bool,
            fault: str | None = None):
    """(exit code, result object or None) of one run of one cell."""
    sys.path.insert(0, ROOT)
    from benchmark.lib import cell as cell_mod
    cell = cell_mod.load_cell(ROOT, workload)
    try:
        result = cell_mod.run_cell(ROOT, cell, seed, seconds, trace,
                                   T_START, fault=fault)
        cell_mod.report_compared(result["checks"])
        return 0, result
    except cell_mod.MeasuredNothing as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return cell_mod.EXIT_MEASURED_NOTHING, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rc, result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
