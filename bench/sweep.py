"""Find the highest arrival rate a serving cell sustains, once, on the chip.

    python bench/sweep.py --workload <serving cell> --seed <n>
        --rates 2,3,4,6,8 [--seconds 30]

One process: the cell's set-up once, then one window per rate at the
cell's traffic shapes (``requests`` scaled to the rate).  For each rate it
prints the tails, the completed request rate and whether the backlog
grew: the time to first token of the last quarter of requests against the
first quarter.  The knee is the highest rate whose backlog did not grow;
a cell below the knee runs at about four fifths of it.  The benchmark's
own runs never do this.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import arrivals, run  # noqa: E402
from bench.common import (  # noqa: E402
    Spans, import_program, quantile, use_compile_cache,
)
from bench.kinds import serve  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    use_compile_cache()
    import_program()
    spec = run.load_spec()
    w = run.find_workload(spec, args.workload)
    cfg = run.load_json(run.BENCH_DIR, "configs", f"{w['config']}.json")
    mix = run.load_json(run.BENCH_DIR, "traffic", f"{w['traffic']}.json")
    cell = serve.Cell(args.workload, cfg, mix, args.seed, Spans(), "",
                      w["chips"])
    cell.setup()
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.mix = dict(mix, rate_per_s=rate,
                        requests=int(rate * args.seconds))
        cell.schedule = arrivals.schedule(cell.mix, args.seed,
                                          cfg["vocab_size"])
        rec = cell.window(args.seconds, False)
        ttft = rec["ttft_ms"]
        q = max(1, len(ttft) // 4)
        first, last = statistics.median(ttft[:q]), statistics.median(
            ttft[-q:])
        print(json.dumps({
            "rate_per_s": rate, "requests": len(ttft),
            "failed": rec["failed"],
            "ttft_p50_ms": quantile(ttft, 0.5),
            "ttft_p90_ms": quantile(ttft, 0.9),
            "itl_p95_ms": quantile(rec["itl_ms"], 0.95)
            if rec["itl_ms"] else None,
            "ttft_first_quarter_ms": first, "ttft_last_quarter_ms": last,
            "backlog_grew": last > 2 * first + 500,
            "lateness_max_s": max(rec["lateness_s"], default=0.0),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
