"""Tokens of every step the window ran, over the whole window, the
checkpoint saves that fall in it included."""


def read(rec):
    return rec["tokens"] / rec["window_s"]
