"""Seconds of XLA compilation in set-up, persistent-cache reads included,
summed from JAX's own compile events."""

UNIT = "s"
LAYER = "process and compile cache"
SOURCE = "program_span"
MOVES = "setup_s"


def read(facts: dict):
    return facts.get("compile_s")
