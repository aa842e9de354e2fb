"""Useful conv operations per second over the traced window, as a share of
the cell's chips' peak (``bench/peaks.json``)."""

UNIT = "%"
LAYER = "whole call"
SOURCE = "device_trace"
MOVES = "images_per_s"


def read(facts: dict):
    trace, calls = facts.get("trace"), facts.get("traced_calls")
    if trace is None or not calls or trace.window_s <= 0:
        return None
    peak = facts["peak"]["flops_per_s"] * trace.n_devices
    return 100.0 * facts["flops_per_call"] * calls / trace.window_s / peak
