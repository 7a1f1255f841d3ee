"""p95 of the gaps between consecutive tokens of a request, in the window."""
from bench.common import quantile


def read(rec):
    return quantile(rec["itl_ms"], 0.95) if rec["itl_ms"] else None
