"""p90 of time to first token, from each request's due time; a request
that never got one counts with the time it waited until the run gave up."""
from bench.common import quantile


def read(rec):
    return quantile(rec["ttft_ms"], 0.90)
