"""What every driver shares: the run's context, compile clock, seeds, weights,
traced sub-window and the sample of answers kept for the check.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cnn import make_cnn

from . import trace as trace_mod

# XLA compilation, persistent-cache reads included (tracing is not cached)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a --trace 1 run profiles the window's first calls, until this many seconds
TRACE_SECONDS = 3.0
# independent streams drawn from one --seed
STREAM_WEIGHTS, STREAM_IMAGES, STREAM_LAYER_INPUTS, STREAM_SAMPLE = range(4)


class CompileClock:
    """Seconds of XLA compilation in this process, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


@dataclasses.dataclass
class Run:
    """One run of one cell: its inputs, and what the readers of metrics read."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    clock: CompileClock
    t0: float  # process start, host clock
    facts: dict = dataclasses.field(default_factory=dict)
    _window_t0: float | None = None
    _window_c0: float = 0.0

    def mark(self, phase: str) -> None:
        """Seconds from process start to the end of a phase of set-up."""
        self.facts.setdefault("setup_phases", {})[phase] = time.perf_counter() - self.t0

    def start_window(self) -> float:
        now = time.perf_counter()
        self._window_t0 = now
        self._window_c0 = self.clock.seconds
        self.facts["setup_s"] = now - self.t0
        self.facts["compile_s"] = self.clock.seconds
        return now

    def end_window(self) -> None:
        self.facts["window_s"] = time.perf_counter() - self._window_t0
        self.facts["window_compile_s"] = self.clock.seconds - self._window_c0

    def read_peak_bytes(self) -> None:
        """Peak bytes in use on the fullest of the cell's chips."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        self.facts["memory_peak_bytes"] = int(max(peaks))


@dataclasses.dataclass
class Outcome:
    """What a driver measured in the window, and what its check compared."""

    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value
    checks: dict  # short name -> (number compared, its limit); passes at value <= limit


def program_model(config: dict):
    """The program's runnable model for a configuration, held to the file's table."""
    model = make_cnn(config["network"], scale=config["scale"])
    table = [[sp.name, sp.h_out, sp.w_out, sp.c_in, sp.r, sp.s, sp.k, sp.stride] for sp in model.specs]
    if table != config["layers"]:
        raise ValueError(f"the program's {config['network']} layers differ from {config['name']}'s layer table")
    return model


def seed_words(seed: int, stream: int) -> list[int]:
    """A seed of any size as 32-bit words, with the stream it feeds."""
    seed = int(seed)
    return [stream, seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, 1 if seed < 0 else 0]


def jax_key(seed: int, stream: int) -> jax.Array:
    key = jax.random.PRNGKey(0)
    for w in seed_words(seed, stream):
        key = jax.random.fold_in(key, w)
    return key


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, stream))


def normal_parts(key: jax.Array, shapes: list[tuple]) -> list[jax.Array]:
    """Standard normal arrays of the given shapes, cut from one draw: one
    random-number op compiles in a second, where one per array takes tens."""
    sizes = [math.prod(s) for s in shapes]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    offsets = np.cumsum([0] + sizes)
    return [flat[a:b].reshape(s) for a, b, s in zip(offsets[:-1], offsets[1:], shapes)]


def he_weights(rows: list[dict], seed: int, device=None) -> list[dict]:
    """He-scaled conv weights (std sqrt(2/fan_in)) and small biases (std 0.1),
    made on the device in one jitted call from the seed, in float32 as they
    are served."""
    shapes = []
    for r in rows:
        shapes += [(r["r"], r["s"], r["c_in"], r["k"]), (r["k"],)]

    def make(key):
        parts = normal_parts(key, shapes)
        return [
            {"w": w * math.sqrt(2.0 / (r["r"] * r["s"] * r["c_in"])), "b": 0.1 * b}
            for r, w, b in zip(rows, parts[0::2], parts[1::2])
        ]

    key = jax_key(seed, STREAM_WEIGHTS)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.block_until_ready(jax.jit(make)(key))


class Reservoir:
    """A uniform sample of at most ``size`` items of a stream of unknown length,
    drawn from the seed, so that the check reads answers from the whole window."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.items: list = []
        self.seen = 0
        self._rng = host_rng(seed, STREAM_SAMPLE)

    def offer(self, make_item) -> None:
        """Keep the item ``make_item()`` builds, if the sample takes it."""
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(make_item())
            return
        j = int(self._rng.integers(self.seen))
        if j < self.size:
            self.items[j] = make_item()


class TracedWindow:
    """Profiles the window's first units of work in a --trace 1 run.

    ``tick()`` after each unit stops the profiler once ``TRACE_SECONDS`` have
    passed; the trace is written under TMPDIR and removed once reduced.
    """

    def __init__(self, run: Run):
        self.on = run.trace
        self.units = 0  # units of work done while the profiler ran
        self.seconds = 0.0  # host seconds the profiler ran
        self.active = False
        self._dir: str | None = None
        self._t0 = 0.0

    def start(self) -> None:
        if not self.on:
            return
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # the drivers' spans and the runtime's main events
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self.active = True
        self._t0 = time.perf_counter()

    def tick(self) -> None:
        if not self.active:
            return
        self.units += 1
        if time.perf_counter() - self._t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        if self.active:
            self.seconds = time.perf_counter() - self._t0
            jax.profiler.stop_trace()
            self.active = False

    def reduce(self, scopes: list[str], n_devices: int, hlo_texts=()) -> trace_mod.Reduction | None:
        """The trace reduced to device time, or None where nothing was traced.
        ``hlo_texts``: the compiled text of the programs whose layers count."""
        if self._dir is None:
            return None
        self.stop()
        try:
            paths = sorted(Path(self._dir).glob("**/*.xplane.pb"))
            return trace_mod.reduce_file(paths[-1], scopes, n_devices, hlo_texts) if paths else None
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
