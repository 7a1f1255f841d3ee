"""Mean host milliseconds of a tick that admitted nothing and decoded."""


def read(rec):
    pure = [d for d, a, n in rec["ticks"] if a == 0 and n > 0]
    return 1e3 * sum(pure) / len(pure) if pure else None
