"""Seconds from process start to the window's start: imports, weights,
engine, compiles or cache loads, warm-up, and the traffic's ramp."""


def read(rec):
    return rec.setup_s
