"""Run one cell with ``--trace 1``, and read the program's own spans too.

    python3 -m bench.program_run --workload resnet50.tune.1chip --seed 7 --seconds 30

It runs ``bench.run`` as the benchmark does, with one difference: the
traced window also reduces its trace with ``bench/program_trace.py``
before the trace is removed.  After ``bench.run``'s result line it prints
one more JSON line: the program's per-layer metrics that move one of the
cell's end-to-end metrics (``program_trace.METRICS``), its breakdown, the
time the device plane's other lines cover in the in-program idle time, the
count of each program span, and what the profiler costs, as units of work
(calls or tunes) per second inside the traced seconds against the rest of
the window.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import Counter
from pathlib import Path

from . import run  # first: it puts the program's src/ on the path
from . import harness, program_trace  # noqa: E402


class ProgramWindow(harness.TracedWindow):
    """A traced window whose trace ``program_trace`` reads as well."""

    def __init__(self, cell_run: harness.Run):
        super().__init__(cell_run)
        self.facts = cell_run.facts

    def reduce(self, scopes, n_devices: int, hlo_texts=()):
        if self._dir is not None:
            self.stop()
            paths = sorted(Path(self._dir).glob("**/*.xplane.pb"))
            if paths:
                self.facts["program_trace"] = program_trace.reduce_file(paths[-1], scopes, n_devices, hlo_texts)
        return super().reduce(scopes, n_devices, hlo_texts)


def tracing_cost(facts: dict) -> dict:
    units = facts.get("calls", facts.get("tunes"))
    traced = facts.get("traced_calls", facts.get("traced_tunes"))
    rest_s = facts["window_s"] - facts["traced_s"]
    return {
        "traced_per_s": traced / facts["traced_s"] if facts["traced_s"] > 0 else None,
        "untraced_per_s": (units - traced) / rest_s if rest_s > 0 else None,
        "window_per_s": units / facts["window_s"],
    }


def program_line(workload: str, facts: dict) -> dict:
    moves = {m["name"] for m in run.cell_spec(workload)["end_to_end"]}
    r = facts.get("program_trace")
    metrics = {}
    if r is not None:
        for name, (unit, moved, read) in program_trace.METRICS.items():
            value = read(r) if moved in moves else None
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
    line = {"program_metrics": metrics, "tracing": tracing_cost(facts)}
    if r is not None:
        line["device"] = {
            "window_s": r.window_s,
            "busy_s": r.busy_s,
            "in_program_idle_s": r.in_program_idle_s,
            "machinery_s": r.machinery_s,
        }
        line["lines_in_program_idle"] = r.line_s_in_program_idle
        line["program_spans"] = dict(Counter(s.name for s in r.program_spans))
        line["program_breakdown"] = r.breakdown()
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    windows: list[ProgramWindow] = []

    def window(cell_run: harness.Run) -> ProgramWindow:
        windows.append(ProgramWindow(cell_run))
        return windows[-1]

    traced_window = harness.TracedWindow
    harness.TracedWindow = window  # the drivers build their window through the module
    try:
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds), "--trace", "1"])
    finally:
        harness.TracedWindow = traced_window
    print(json.dumps(program_line(args.workload, windows[0].facts)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
