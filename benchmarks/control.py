"""The control of `correct`: a cell's run with the control put in the
program's place, which has to come out as NOT correct.

    python3 benchmarks/control.py --workload <cell> --seeds <n,n,...> --seconds <s>

The control is the plain reference computed in the nearest precision below
the configuration's (float8 for bfloat16).  The kind does everything a run
does, on the chip and at the cell's own sizes, then compares the control's
readings where it would compare the program's (`RunContext.control`).  One
result line per seed, the same object `run.py` prints; exits 0 when every
seed read `correct: false`, 1 when the control passed anywhere.  The
benchmark's own runs never call this; a short window is enough (long enough
to finish the mix's longest requests, where the cell serves).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run as R  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="whole numbers, comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        result = R.run_on_chip(args.workload, seed, args.seconds, control=True,
                               t_start=time.perf_counter())
        R.report(result)
        if result["correct"]:
            passed.append(seed)
    if passed:
        print(f"benchmarks/control.py: the control read correct on seeds {passed}",
              file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
