"""Peak device memory in use on the fullest of the cell's chips, after the
window, from the runtime's own counter."""

UNIT = "GB"
LAYER = "PipelineRunner"
SOURCE = "program_counter"
MOVES = "images_per_s"


def read(facts: dict):
    peak = facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
