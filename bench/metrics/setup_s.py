"""Seconds from the process's start to the window's start: backend start,
compile-cache loads, inputs and warm calls."""


def read(rec):
    return rec.setup_s
