"""The control of ``correct``: the plain reference put in the program's
place, computed in float32 where the configuration's guarantee is exact
fixed-point.  It has to come out as NOT correct; a comparison that lets it
pass would let a later PR trade exactness for speed unseen.

    python benchmarks/chip/control.py --workload <name> --seeds 1 2 3

Needs no chip (it is pandas on the host), but runs at the cell's own size on
the data of the same seeds; the benchmark's own runs never run it.  Prints
one JSON line per seed with the numbers compared and ``correct``, and exits
0 only if every seed came out as not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if __package__ in (None, ""):
    sys.path.insert(0, ROOT)
    __package__ = "benchmarks.chip"

from . import compare  # noqa: E402
from .run import cell_data, load_cell, oracle_answers  # noqa: E402


def control_verdict(workload: str, seed: int, scale=None) -> dict:
    """One pass of the mix answered by the float32 reference, judged as a
    window's answers are."""
    spec = load_cell(workload)
    queries, config = spec["queries"], spec["config"]
    ddir, _, _ = cell_data(spec, seed, scale, owner=f"control-{workload}")
    try:
        exact = oracle_answers(queries, ddir)
        low = oracle_answers(queries, ddir, money="float32")
    finally:
        shutil.rmtree(ddir, ignore_errors=True)
    answers = []
    for q in queries:
        rows, _, limit = low[q["name"]]
        answers.append((q["name"], rows[:limit] if limit else rows))
    return compare.judge(answers, exact, config["correct"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args(argv)
    passed = []
    for seed in args.seeds:
        v = control_verdict(args.workload, seed, args.scale)
        print(json.dumps({"control": "float32", "workload": args.workload,
                          "seed": seed, "correct": v["correct"],
                          "compared": v["numbers"],
                          "first_fault": v["first_fault"]}), flush=True)
        if v["correct"]:
            passed.append(seed)
    if passed:
        print(f"the control came out as correct on seeds {passed}: the "
              "comparison does not hold the guarantee", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
