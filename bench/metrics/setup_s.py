"""Seconds from process start to the window's start: imports, device
start-up, weights, compilation or compile-cache loads, the warm-up of
every shape and the traffic's own warm-up."""


def compute(rec):
    return rec.setup_s
