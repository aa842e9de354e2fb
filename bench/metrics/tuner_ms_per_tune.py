"""Host milliseconds per tune spent in the seed and the tuner (run_shisha),
mean over the window's tunes."""

UNIT = "ms"
LAYER = "seed and tuner"
SOURCE = "host_clock"
MOVES = "tune_s"


def read(facts: dict):
    times = facts.get("tuner_s")
    return 1e3 * sum(times) / len(times) if times else None
