"""Share of the layers' device time spent on their skip edges: the device
time of the operations under ``shisha.skip`` (route concats, shortcut adds,
upsamples, and the canonical's slots of tensors a later layer reads) over
the device time under the layers' named scopes, summed over the cell's
chips.  Nothing where the program has no ``shisha.skip`` scope."""

UNIT = "%"
LAYER = "skip edges"
SOURCE = "device_trace"
MOVES = "images_per_s"


def read(facts: dict):
    trace, skip_s = facts.get("trace"), facts.get("skip_s")
    if trace is None or skip_s is None:
        return None
    scoped = sum(trace.scope_s.values())
    return 100.0 * skip_s / scoped if scoped > 0 else None
