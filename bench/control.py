#!/usr/bin/env python3
"""The control of one cell, and sound runs beside it, in one process.

    python3 bench/control.py --workload <name> --control-seeds 1,2,3 \
        [--sound-seeds 4,5,6] --seconds 10

The control is the cell run as usual, except that the store's model is
served with its weights rounded to bfloat16 (the step below the float32
the configuration states) against the ``T_aux`` of its float32 build.
It has to read not correct.  The sound runs are ordinary runs of the
cell on further seeds.  Each run prints its result line as
``bench/run.py`` does; the benchmark's own runs never run the control.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import argparse  # noqa: E402

from bench import harness  # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description="Run a cell's control and sound runs.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-seeds", type=seeds, required=True)
    ap.add_argument("--sound-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    readings = []
    for control, group in ((True, args.control_seeds), (False, args.sound_seeds)):
        for seed in group:
            r = harness.run(args.workload, seed, args.seconds, False, control=control)
            readings.append((control, seed, r["correct"],
                             {k: c["value"] for k, c in r["checks"].items()}))
    for control, seed, correct, checks in readings:
        print(f"{'control' if control else 'sound'} seed {seed} correct {correct} {checks}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
