"""The conv layers' share of their roofline: the least time the chip could
take for the traced calls' layers (``bench/work.py``, at the layers' real
shapes, against the peak table) over the device time under the layers'
named scopes, summed over the cell's chips."""

UNIT = "%"
LAYER = "layer ops"
SOURCE = "device_trace"
MOVES = "images_per_s"


def read(facts: dict):
    trace, calls = facts.get("trace"), facts.get("traced_calls")
    if trace is None or not calls:
        return None
    scoped = sum(trace.scope_s.values())
    if scoped <= 0:
        return None
    return 100.0 * facts["least_s_per_call"] * calls / scoped
