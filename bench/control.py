#!/usr/bin/env python3
"""Readings that set the limit of the served-logit-gap check, on the chip.

  python3 bench/control.py --workload qwen3-4b.chat --seconds 15 \\
      --seeds 11,12,13,14,15,16,17,18,19,20,21,22 --control-seeds 3

Runs the cell once per seed in one process, at the cell's own sizes and
load, through the same path as ``bench/run.py``, and prints one JSON line
per seed: the program's widest served-token gap against the float32
reference (the lower reading) and its ``correct``; for the first
``--control-seeds`` seeds also the fp8 control's: the widest gap of its
first choices at the same positions (the upper reading) and the verdict
of the same checks that decide ``correct`` (which has to be false). The
benchmark's own runs never run the control.
"""

import argparse
import json
import sys

from run import ROOT, configure, execute  # noqa: I001 (bench/ is on the path)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    configure()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        res, lines = execute(ROOT, args.workload, seed, args.seconds, False,
                             control=i < args.control_seeds)
        for line in lines:
            print(line, file=sys.stderr, flush=True)
        ctl = res.get("control", {})
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program_gap": res["checks"]["served_logit_gap"]["value"],
            "control_correct": ctl.get("correct"),
            "control_gap": ctl.get("checks", {}).get(
                "served_logit_gap", {}).get("value"),
            "metrics": res["metrics"], "checks": res["checks"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
