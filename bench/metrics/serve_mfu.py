"""Prefill and decode model operations the window completed, over the
window and the chips' bf16 peak, in percent."""
from bench.peaks import peak


def read(rec):
    if rec["platform"] != "tpu":
        return None     # no chip, no share of its peak
    return 100.0 * rec["flops"] / (rec["window_s"] * rec["chips"]
                                   * peak(rec["device_kind"], "bf16_flops"))
