#!/usr/bin/env python3
"""The control: a cell run with one guarantee broken UNDER the timed
path, which has to come out as not correct.

    python3 benchmark/control_run.py --workload <cell> --seed <n>
                                     --seconds <s> --fault accept_all

Same harness, same sizes, same check as `run.py` (it calls run.py's own
`execute`); the only difference is the fault (`lib/faults.py`).  The
benchmark's own runs never run this.  It exits 0 when the check caught
the fault (`correct` false) and 1 when the broken run passed.
"""

import run as bench_run          # first: set-up is counted from its import

import argparse                  # noqa: E402
import json                      # noqa: E402
import sys                       # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", required=True)
    args = ap.parse_args(argv)
    rc, result = bench_run.execute(args.workload, args.seed, args.seconds,
                                   False, fault=args.fault)
    if result is None:
        return rc or 1
    print(json.dumps(result), flush=True)
    caught = result["correct"] is False
    print(f"control: fault {args.fault!r} "
          f"{'caught: correct is false' if caught else 'NOT CAUGHT'}",
          flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
