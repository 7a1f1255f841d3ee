"""Mean device milliseconds of one run of the jitted train step (device 0),
from the trace."""


def read(rec):
    steps = (rec.get("trace") or {}).get("step_s")
    return 1e3 * sum(steps) / len(steps) if steps else None
