"""Readings the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 [--controls 3]
        [--seconds 5]

For each seed: the cell's set-up, a short window at the cell's own load,
and its check, printing every number compared.  For the first
``--controls`` seeds it also reads the control (the reference in float8)
and, for training cells, the fault of a loss over half of the rows.  One
process serves all seeds, so programs compile once.  The benchmark's own
runs never do this.  Output: one JSON line per seed, and a summary line
with the largest program reading and the smallest control reading of each
number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402
from bench.common import BenchError, use_compile_cache  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    prog: dict = {}
    ctl: dict = {}
    for k, seed in enumerate(seeds):
        try:
            out = run.measure(args.workload, seed, args.seconds, False,
                              controls=k < args.controls)
        except BenchError as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        nums = dict(out["_info"])
        nums.update({n: c["value"] for n, c in out["checks"].items()})
        print(json.dumps({"seed": seed, "numbers": nums,
                          "metrics": out["metrics"],
                          "correct": out["correct"]}), flush=True)
        for n, v in nums.items():
            if v is None:
                continue
            if "." in n:
                ctl[n] = min(ctl.get(n, v), v)
            else:
                prog[n] = max(prog.get(n, v), v)
    print(json.dumps({"program_max": prog, "control_min": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
