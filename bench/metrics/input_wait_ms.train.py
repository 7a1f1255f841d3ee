"""Mean host milliseconds of ``DataPipeline.next_batch`` per step."""


def read(rec):
    s = rec.get("input_s")
    return 1e3 * sum(s) / len(s) if s else None
