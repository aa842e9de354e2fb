"""The program's own spans and scopes in a profiler trace.

``bench/trace.py`` reduces a trace to device time over the window that the
drivers' host spans mark.  The program marks its own work on the same
clock, every name starting with ``shisha.`` (``PROGRAM_PREFIX``):

- host spans (``jax.profiler.TraceAnnotation``): ``shisha.run`` around each
  ``PipelineRunner.run`` dispatch; ``shisha.measure`` around
  ``MeasuringEvaluator``'s layer loop and, inside it for each layer,
  ``shisha.measure.warm`` (``layer``: the jit wrapper, the first call and
  its sync) and ``shisha.measure.timed`` (``layer``, ``calls``: the timed
  calls, each synced);
- device scopes (``jax.named_scope``, segments of an operation's op name
  path): ``shisha.init``, ``shisha.inject``, ``shisha.emit``,
  ``shisha.ship`` and ``shisha.gather`` in ``PipelineRunner``'s program,
  and ``shisha.canon`` around the canonical pad, slice and crop
  (``RUNNER_SCOPES``).

``reduce`` reads them over ``bench/trace.py``'s window and busy time, which
it computes by the same rules from the same operations, so that module's
numbers stand as they are.  On top of them:

- in-program idle: the part of a device's idle time that falls inside one
  of its ``XLA Modules`` intervals, where a program was running but no
  operation was; the rest of the idle time the device waited on the host;
- machinery: the union of the intervals of operations whose path has a
  runner scope among its segments;
- each idle gap goes to the innermost span, the program's or the driver's,
  that covers its middle; so does each stretch of idle time outside every
  program;
- an operation is named by its layer, else by its innermost ``shisha.*``
  scope, else by the last step of its path;
- for each line of a device plane other than ``XLA Ops`` and ``XLA
  Modules``, the time its events cover inside in-program idle gaps: work
  that the ops line does not show.

``METRICS`` computes four per-layer metrics from a reduction.  The harness
does not call this module: ``bench/program_run.py`` runs a cell with it.
"""

from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from pathlib import Path

from . import trace

PROGRAM_PREFIX = "shisha."
RUNNER_SCOPES = ("shisha.init", "shisha.inject", "shisha.emit", "shisha.ship", "shisha.gather", "shisha.canon")
WARM, TIMED = "shisha.measure.warm", "shisha.measure.timed"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # seconds on the trace's clock
    end: float
    meta: dict  # the span's metadata (event stats), e.g. {"layer": 3, "calls": 3}


@dataclasses.dataclass
class Device:
    """What one device plane holds besides its operations."""

    modules: list  # (start, end, module) of line XLA Modules
    lines: dict  # line name -> [(start, end)], for every other line but XLA Ops


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def place(op: trace.Op, scopes) -> str:
    """The op's layer, else its innermost ``shisha.*`` scope, else its path's last step."""
    steps = op.path.split("/")
    layer = next((s for s in steps if s in scopes), None)
    if layer:
        return layer
    own = [s for s in steps if s.startswith(PROGRAM_PREFIX)]
    return own[-1] if own else steps[-1]


class SpanIndex:
    """``trace.span_at`` over many spans: each span is kept in every bucket
    of ``width`` seconds that it touches, so a lookup reads one bucket."""

    def __init__(self, spans: list[tuple[float, float, str]], width: float = 1e-3):
        self.width = width
        self.buckets: dict = defaultdict(list)
        for a, b, name in spans:
            for k in range(int(a // width), int(b // width) + 1):
                self.buckets[k].append((a, b, name))

    def at(self, t: float) -> str:
        return trace.span_at(self.buckets.get(int(t // self.width), []), t)


@dataclasses.dataclass
class Reduction:
    n_devices: int
    window_s: float
    busy_s: float  # mean over the devices, as bench/trace.py's
    in_program_idle_s: float  # mean over the devices
    machinery_s: float  # mean over the devices
    program_spans: list  # [Span], by start
    op_s: dict  # "<layer, shisha scope or op name>/<kind>" -> device seconds, summed over the devices
    idle_by_span: dict  # innermost span -> idle device seconds, mean over the devices
    idle_between_programs: dict  # the same for the idle time outside every program
    idle_in_program: dict  # module -> in-program idle device seconds, mean over the devices
    line_s_in_program_idle: dict  # device line -> seconds of its events in in-program idle, mean over the devices
    busy: dict  # device -> sorted disjoint [(start, end)] busy intervals in the window

    def busy_in(self, start: float, end: float) -> float:
        """Device busy seconds inside [start, end], mean over the devices."""
        total = 0.0
        for d in range(self.n_devices):
            busy = self.busy.get(d, [])
            k = max(bisect.bisect_right(busy, (start,)) - 1, 0)
            while k < len(busy) and busy[k][0] < end:
                total += max(0.0, min(busy[k][1], end) - max(busy[k][0], start))
                k += 1
        return total / self.n_devices

    def spans(self, name: str) -> list:
        return [s for s in self.program_spans if s.name == name]

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[: trace.BREAKDOWN_ENTRIES]]

        return {
            "device_ops": top(self.op_s),
            "idle_gaps": top(self.idle_by_span),
            "idle_between_programs": top(self.idle_between_programs),
            "idle_in_program": top(self.idle_in_program),
        }


def reduce(ops, host_spans, program_spans, devices: dict, scopes, n_devices: int) -> Reduction | None:
    """The program's reading of ``n_devices`` devices over the drivers' window.

    ``ops`` and ``host_spans`` as ``trace.read_profile`` gives them,
    ``program_spans`` a list of ``Span``, ``devices`` device id -> ``Device``."""
    ops = [op for op in ops if op.device < n_devices]
    if not ops:
        return None
    if host_spans:
        lo, hi = min(a for a, _, _ in host_spans), max(b for _, b, _ in host_spans)
    else:
        lo, hi = min(op.start for op in ops), max(op.end for op in ops)
    scopes = set(scopes)
    runner = set(RUNNER_SCOPES)
    op_s: dict = defaultdict(float)
    per_device: dict = defaultdict(list)
    machinery: dict = defaultdict(list)
    for op in ops:
        part = trace.clip([(op.start, op.end)], lo, hi)
        if not part:
            continue
        per_device[op.device].append(part[0])
        kind = trace.op_kind(op.name)
        if kind in trace.CONTAINERS:
            continue
        where = place(op, scopes)
        op_s[f"{where}/{kind}" if where else kind] += part[0][1] - part[0][0]
        if runner.intersection(op.path.split("/")):
            machinery[op.device].append(part[0])
    index = SpanIndex(list(host_spans) + [(s.start, s.end, s.name) for s in program_spans])
    busy: dict = {}
    idle_by_span: dict = defaultdict(float)
    between: dict = defaultdict(float)
    idle_in_program: dict = defaultdict(float)
    line_s: dict = defaultdict(float)
    in_program_total = machinery_total = busy_total = 0.0
    for d in range(n_devices):
        busy[d] = trace.union(per_device.get(d, []))
        busy_total += length(busy[d])
        machinery_total += length(trace.union(machinery.get(d, [])))
        idle = trace.gaps(busy[d], lo, hi)
        for a, b in idle:
            idle_by_span[index.at((a + b) / 2)] += (b - a) / n_devices
        device = devices.get(d, Device([], {}))
        in_program = []
        for module in sorted({m for _, _, m in device.modules}):
            running = trace.union(trace.clip([(a, b) for a, b, m in device.modules if m == module], lo, hi))
            inside = intersect(idle, running)
            idle_in_program[module] += length(inside) / n_devices
            in_program += inside
        in_program = trace.union(in_program)
        in_program_total += length(in_program)
        for a, b in trace.gaps(trace.union(busy[d] + in_program), lo, hi):
            between[index.at((a + b) / 2)] += (b - a) / n_devices
        for line, events in device.lines.items():
            line_s[line] += length(intersect(in_program, trace.union(events))) / n_devices
    return Reduction(
        n_devices=n_devices,
        window_s=hi - lo,
        busy_s=busy_total / n_devices,
        in_program_idle_s=in_program_total / n_devices,
        machinery_s=machinery_total / n_devices,
        program_spans=sorted(program_spans, key=lambda s: (s.start, -s.end, s.name)),
        op_s=dict(op_s),
        idle_by_span=dict(idle_by_span),
        idle_between_programs=dict(between),
        idle_in_program=dict(idle_in_program),
        line_s_in_program_idle=dict(line_s),
        busy=busy,
    )


def read_program(path: Path) -> tuple[list[Span], dict]:
    """The program's host spans and each device plane's modules and other
    lines, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: list[Span] = []
    devices: dict = {}
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            device = Device([], {})
            for line in plane.lines:
                events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name) for e in line.events]
                if line.name == trace.MODULES_LINE:
                    device.modules = [(a, b, trace.module_key(name)) for a, b, name in events]
                elif line.name != trace.OPS_LINE:
                    device.lines[line.name] = [(a, b) for a, b, _ in events]
            devices[int(m.group(1))] = device
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        start = e.start_ns * 1e-9
                        spans.append(Span(e.name, start, start + e.duration_ns * 1e-9, dict(e.stats)))
    return spans, devices


def reduce_file(path: Path, scopes, n_devices: int, hlo_texts=()) -> Reduction | None:
    ops, host_spans = trace.read_profile(path, trace.op_names(hlo_texts))
    spans, devices = read_program(path)
    return reduce(ops, host_spans, spans, devices, scopes, n_devices)


# --- the four per-layer metrics; each None where it finds nothing to read ---


def runner_machinery_share(r: Reduction):
    """Device time under the runner's scopes, as a share of busy time."""
    return 100.0 * r.machinery_s / r.busy_s if r.machinery_s > 0 and r.busy_s > 0 else None


def device_idle_in_program(r: Reduction):
    """Idle time inside a running program, as a share of the window."""
    return 100.0 * r.in_program_idle_s / r.window_s if r.idle_in_program and r.window_s > 0 else None


def measure_warm_share(r: Reduction):
    """The warm spans' share of the warm and timed spans' time."""
    warm = sum(s.end - s.start for s in r.spans(WARM))
    timed = sum(s.end - s.start for s in r.spans(TIMED))
    return 100.0 * warm / (warm + timed) if warm + timed > 0 else None


def sync_us_per_layer_call(r: Reduction):
    """Host time of a timed layer call that the device was not busy, in µs."""
    timed = r.spans(TIMED)
    calls = sum(int(s.meta.get("calls", 0)) for s in timed)
    if not calls:
        return None
    return 1e6 * sum(s.end - s.start - r.busy_in(s.start, s.end) for s in timed) / calls


# name -> (unit, the end-to-end metric it should move, reader)
METRICS = {
    "runner_machinery_share": ("%", "images_per_s", runner_machinery_share),
    "device_idle_in_program.serve": ("%", "images_per_s", device_idle_in_program),
    "measure_warm_share": ("%", "tune_s", measure_warm_share),
    "sync_us_per_layer_call": ("us", "tune_s", sync_us_per_layer_call),
}
