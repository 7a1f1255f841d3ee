"""Share of the traced window in which no op ran on the device, in percent,
in the checkpoint cell."""
from bench.trace import idle_percent


def read(rec):
    return idle_percent(rec.get("trace"))
