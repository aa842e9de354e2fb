"""One reduction from a profiler trace (``.xplane.pb``) to device time.

Every trace reader of the benchmark goes through ``reduce``:

- the window is the host's work in the trace: from the start of the first
  host span that the drivers mark (``HOST_SPANS``) to the end of the last;
- a device's busy time is the union of the intervals in which an operation
  ran on it (line ``XLA Ops`` of its plane), clipped to the window;
- the chip's trace names each operation by its HLO instruction
  (``%fusion.8 = f32[...] fusion(...)``) and not by its op name path; the
  path (the ``jax.named_scope`` stack) comes from the compiled program's
  text, for the module (line ``XLA Modules``) that was running around it;
- an operation belongs to a layer when a segment of its path is that
  layer's name.  Control flow (``while``, ``conditional``, ``call``) holds
  other operations and is counted in busy time only;
- collectives are the operations whose name says so (``COLLECTIVES``);
- each idle gap of a device is put down to the host span that covers its
  middle, so that the breakdown says what the host was doing meanwhile.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("h2d", "pad", "call", "crop_d2h", "measure", "tuner")
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather", "reduce-scatter", "all-to-all", "send", "recv")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
OP_NAME = re.compile(r'op_name="([^"]*)"')
BREAKDOWN_ENTRIES = 10


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str  # HLO instruction name, e.g. fusion.8
    path: str  # op name path, "" where unknown
    start: float  # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Reduction:
    n_devices: int
    window_s: float
    busy_s: float  # mean over the devices
    scope_s: dict  # layer name -> device seconds, summed over the devices
    collective_s: float  # summed over the devices
    op_s: dict  # "<layer or op name>/<kind>" -> device seconds, summed over the devices
    idle_by_span: dict  # host span -> idle device seconds, mean over the devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:BREAKDOWN_ENTRIES]]

        return {"device_ops": top(self.op_s), "idle_gaps": top(self.idle_by_span)}


def op_kind(name: str) -> str:
    """An HLO instruction's name without its number: ``fusion.12`` -> ``fusion``."""
    return re.sub(r"[.\d]+$", "", name) or name


def instruction(text: str) -> str:
    """The instruction's name in a line of HLO text or a trace event's name."""
    m = INSTRUCTION.match(text)
    return m.group(1) if m else text


def module_key(name: str) -> str:
    """``jit_f(1234)`` in a trace and ``HloModule jit_f, ...`` in HLO text -> ``jit_f``."""
    return name.split("(")[0].split(",")[0].replace("HloModule", "").strip()


def op_names(hlo_texts) -> dict:
    """Module -> {instruction -> op name path}, from compiled programs' text."""
    table: dict = {}
    for text in hlo_texts:
        lines = text.splitlines()
        names = table.setdefault(module_key(lines[0]), {})
        for line in lines[1:]:
            m = OP_NAME.search(line)
            if m:
                names[instruction(line)] = m.group(1)
    return table


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(kind.startswith(c) for c in COLLECTIVES)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def span_at(spans: list[tuple[float, float, str]], t: float) -> str:
    """The innermost host span that covers time t, or ``"none"``."""
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "none"


def reduce(ops: list[Op], host_spans: list[tuple[float, float, str]], scopes, n_devices: int) -> Reduction | None:
    """Device time of ``n_devices`` devices (ids 0..n-1) over the host's window."""
    ops = [op for op in ops if op.device < n_devices]
    if not ops:
        return None
    if host_spans:
        lo, hi = min(a for a, _, _ in host_spans), max(b for _, b, _ in host_spans)
    else:
        lo, hi = min(op.start for op in ops), max(op.end for op in ops)
    scopes = set(scopes)
    scope_s: dict = defaultdict(float)
    op_s: dict = defaultdict(float)
    collective_s = 0.0
    per_device: dict = defaultdict(list)
    for op in ops:
        part = clip([(op.start, op.end)], lo, hi)
        if not part:
            continue
        seconds = part[0][1] - part[0][0]
        per_device[op.device].append(part[0])
        kind = op_kind(op.name)
        if kind in CONTAINERS:
            continue
        scope = next((seg for seg in op.path.split("/") if seg in scopes), None)
        # a layer's ops by layer; the rest by the last step of their op name path
        where = scope or op.path.rsplit("/", 1)[-1]
        op_s[f"{where}/{kind}" if where else kind] += seconds
        if is_collective(op.name):
            collective_s += seconds
        if scope:
            scope_s[scope] += seconds
    busy_total = 0.0
    idle_by_span: dict = defaultdict(float)
    for d in range(n_devices):
        busy = union(per_device.get(d, []))
        busy_total += sum(b - a for a, b in busy)
        for a, b in gaps(busy, lo, hi):
            idle_by_span[span_at(host_spans, (a + b) / 2)] += (b - a) / n_devices
    return Reduction(
        n_devices=n_devices,
        window_s=hi - lo,
        busy_s=busy_total / n_devices,
        scope_s=dict(scope_s),
        collective_s=collective_s,
        op_s=dict(op_s),
        idle_by_span=dict(idle_by_span),
    )


def read_profile(path: Path, paths: dict | None = None) -> tuple[list[Op], list[tuple[float, float, str]]]:
    """Device operations and the drivers' host spans of one ``.xplane.pb``;
    ``paths`` is ``op_names(...)`` of the programs that ran."""
    from jax.profiler import ProfileData

    paths = paths or {}
    data = ProfileData.from_file(str(path))
    ops: list[Op] = []
    spans: list[tuple[float, float, str]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            device = int(m.group(1))
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, module_key(e.name)) for e in lines.get(MODULES_LINE, []))
            starts = [a for a, _, _ in modules]
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = modules[i][2] if i >= 0 and e.start_ns <= modules[i][1] else ""
                name = instruction(e.name)
                start = e.start_ns * 1e-9
                ops.append(Op(device, name, paths.get(module, {}).get(name, ""), start, start + e.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        start = e.start_ns * 1e-9
                        spans.append((start, start + e.duration_ns * 1e-9, e.name))
    return ops, spans


def reduce_file(path: Path, scopes, n_devices: int, hlo_texts=()) -> Reduction | None:
    ops, spans = read_profile(path, op_names(hlo_texts))
    return reduce(ops, spans, scopes, n_devices)
