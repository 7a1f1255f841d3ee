"""Mean host seconds of a ``CheckpointManager.save`` in the window."""


def read(rec):
    s = rec.get("ckpt_save_s")
    return sum(s) / len(s) if s else None
