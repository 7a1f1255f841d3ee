"""Model operations of the window's steps over the window and the chips'
bf16 peak, in percent (recomputation not counted)."""
from bench.peaks import peak


def read(rec):
    if rec["platform"] != "tpu":
        return None     # no chip, no share of its peak
    ops = rec["steps"] * rec["step_flops"]
    return 100.0 * ops / (rec["window_s"] * rec["chips"]
                          * peak(rec["device_kind"], "bf16_flops"))
