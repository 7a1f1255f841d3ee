"""Host milliseconds an admission adds to its tick: ticks that admitted
requests, less the run's median pure-decode tick, per request admitted."""
import statistics


def read(rec):
    pure = [d for d, a, n in rec["ticks"] if a == 0 and n > 0]
    adm = [(d, a) for d, a, n in rec["ticks"] if a > 0]
    if not pure or not adm:
        return None
    base = statistics.median(pure)
    return 1e3 * sum(d - base for d, _ in adm) / sum(a for _, a in adm)
