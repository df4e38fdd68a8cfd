"""Seconds from process start to the window's first request."""


def read(rec):
    return rec.setup_s
