"""Host seconds per tune spent building the MeasuringEvaluator, which jits
and times every layer on the chip, mean over the window's tunes."""

UNIT = "s"
LAYER = "MeasuringEvaluator"
SOURCE = "host_clock"
MOVES = "tune_s"


def read(facts: dict):
    times = facts.get("measure_s")
    return sum(times) / len(times) if times else None
