"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``us_per_call`` measures the real
fabric code; ``derived`` is the modeled figure-of-merit (virtual-WAN
seconds / MB/s / fractions), deterministic across runs.

``--smoke`` runs every module at tiny sizes (CI's benchmark job uses it to
keep the scripts from rotting without paying full-size runtimes).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, no figure output (CI fast path)")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    from benchmarks import (
        table1_sizes, fig23_iozone, fig4_build, fig5_largefile,
        fig_replica_read, fig_quorum_write, fig_congestion,
        fig_maintenance, fig_conflict, fig_eviction, fig_bulk,
        sharing_census,
    )

    rc = 0
    for mod in (table1_sizes, fig23_iozone, fig4_build, fig5_largefile,
                fig_replica_read, fig_quorum_write, fig_congestion,
                fig_maintenance, fig_conflict, fig_eviction, fig_bulk,
                sharing_census):
        rc |= int(mod.run(smoke=args.smoke) or 0)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
