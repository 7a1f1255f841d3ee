"""Tokens of every step the window ran, over the whole window."""


def read(rec):
    return rec["tokens"] / rec["window_s"]
