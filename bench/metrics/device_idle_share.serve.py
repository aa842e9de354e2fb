"""Share of the traced serving window in which no operation ran on the
device, mean over the cell's chips."""

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "images_per_s"


def read(facts: dict):
    trace = facts.get("trace")
    return None if trace is None else 100.0 * trace.idle_share
