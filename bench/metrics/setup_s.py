"""Seconds from process start to the window's first timed operation."""


def read(rec):
    return rec["setup_s"]
