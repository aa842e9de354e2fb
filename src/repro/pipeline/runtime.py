"""Shisha-scheduled pipeline runtime.

The paper's deployment story, on JAX: a chain-structured network (the
paper's CNNs, or any LM block stack) is split into N contiguous stages by
a Shisha ``PipelineConfig``; each stage is pinned to one slice of the mesh
("stage" axis = the chiplet axis) and microbatches stream through the
stages with ``jax.lax.ppermute`` — GPipe-style fill/steady/drain, built
with shard_map so every transfer is an explicit neighbour permute (the
paper's inter-chiplet link).

:class:`MeasuringEvaluator` closes the online-tuning loop: it times each
layer on the real device and scales that time by each EP's derate
(hetero.py).  This is the paper's "runtime performance value" — Algorithm 2
consumes it exactly like the gem5 database.  Every evaluator times every
layer afresh, but the compiled layer programs outlive it: a re-tune over the
same layer functions, at the same argument shapes and matmul precision,
reuses them, and times each layer as ``reps`` calls dispatched back to back
and synced once.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from ..core.config import PipelineConfig
from ..core.evaluator import AnalyticEvaluator
from .hetero import EPDerates

# ---------------------------------------------------------------------------
# Measured oracle
# ---------------------------------------------------------------------------


# Every compiled layer program of the process, by layer function and then by
# argument tree, avals, shardings and matmul precision.  Weak on the
# function: a layer function that is dropped takes its programs with it.
_PROGRAMS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def _program_key(args: tuple) -> tuple:
    """What, besides the function, decides the program ``jit`` compiles for ``args``."""
    leaves, tree = jax.tree.flatten(args)
    avals = tuple((jax.typeof(x), getattr(x, "sharding", None)) for x in leaves)
    return tree, avals, jax.config.jax_default_matmul_precision


@dataclasses.dataclass
class MeasuringEvaluator(AnalyticEvaluator):
    """`execute(conf)` backed by per-layer times measured on the device.

    Each layer is timed on the device as ``reps`` calls of its compiled
    program dispatched back to back and synced once (block_until_ready on
    their outputs); its time is that span over ``reps``.  In a pipeline the
    layers run back to back inside one program, so a layer is charged a
    share of one host round trip, not a round trip a call.  A layer's time on
    an EP is its measured time scaled by that EP's derate — the live
    analogue of the paper's gem5 database.  Stage times, throughput and the
    link cost come from AnalyticEvaluator over that table.

    Compiled layer programs are kept for the life of their function and
    shared by every evaluator of the process (``_PROGRAMS``): the first
    evaluator to see a function at given argument shapes, dtypes, shardings
    and matmul precision compiles it and makes one warm call (a miss); a
    later one finds it compiled and loaded and times it at once (a hit).
    Measured times never cross evaluators: each times every layer afresh.
    ``program_hits``, ``program_misses`` and ``syncs`` count this
    evaluator's lookups and its calls to block_until_ready.
    """

    layer_fns: Sequence[Callable] | None = None
    layer_args: Sequence[tuple] | None = None
    reps: int = 3

    def __post_init__(self):
        self.derates = EPDerates.from_platform(self.platform)
        self._measured: list[float] = []
        self.program_hits = self.program_misses = self.syncs = 0
        # profiler spans around, never inside, the timed calls: with the
        # profiler off each costs about a microsecond
        with TraceAnnotation("shisha.measure"):
            for i, (fn, args) in enumerate(zip(self.layer_fns, self.layer_args)):
                programs = _PROGRAMS.setdefault(fn, {})
                key = _program_key(args)
                program = programs.get(key)
                cached = program is not None
                if cached:
                    self.program_hits += 1
                else:
                    self.program_misses += 1
                    with TraceAnnotation("shisha.measure.warm", layer=i):
                        program = programs[key] = jax.jit(fn).lower(*args).compile()
                        jax.block_until_ready(program(*args))  # load + warm
                        self.syncs += 1
                with TraceAnnotation("shisha.measure.timed", layer=i, calls=self.reps, cached=int(cached)):
                    t0 = time.perf_counter()
                    outs = [program(*args) for _ in range(self.reps)]
                    jax.block_until_ready(outs)
                    self._measured.append((time.perf_counter() - t0) / self.reps)
                    self.syncs += 1

    def layer_time_by_index(self, layer_idx: int, ep_idx: int) -> float:
        return self.derates.scale(ep_idx, self._measured[layer_idx]) + self.layer_overhead


# ---------------------------------------------------------------------------
# shard_map GPipe pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PipelineRunner:
    """Runs a layer chain as an N-stage microbatched pipeline.

    ``apply_layer(i, x)`` must map one activation shape to itself, so stages
    of different depth stay branch-compatible under lax.switch: for a CNN
    that is ``canonical_pipeline_apply``'s flat canonical, which holds a
    boundary's whole live set (every tensor a later layer still reads: the
    previous output alone in a chain, several across a residual block or a
    route) at fixed row slots; LM blocks are shape-preserving.  Every buffer
    the runner carries (``micro``, ``buf``, ``outs``) has that shape per
    microbatch, so a cut ships its live set in its one ``ppermute``.

    The program's own steps carry ``shisha.*`` named scopes (``init``,
    ``inject``, ``emit``, ``ship``, ``gather``), which change only op-name
    metadata; each ``run`` is a ``shisha.run`` profiler span.
    """

    mesh: Mesh
    conf: PipelineConfig
    apply_layer: Callable[[int, jax.Array], jax.Array]
    n_micro: int = 8

    def __post_init__(self):
        if self.mesh.shape["stage"] != self.conf.depth:
            raise ValueError(
                f"mesh stage axis {self.mesh.shape['stage']} != pipeline depth {self.conf.depth}"
            )
        bounds = self.conf.boundaries()

        def make_stage(a, b):
            def stage_fn(x):
                for i in range(a, b):
                    x = self.apply_layer(i, x)
                return x
            return stage_fn

        self._stage_fns = [make_stage(a, b) for a, b in bounds]
        # one jitted program per runner: a fresh jax.jit on every call
        # would trace and compile it again each time
        self._run = jax.jit(self._pipelined)

    def _pipelined(self, micro: jax.Array) -> jax.Array:
        """micro: [n_micro, ...activation] replicated. Returns outputs."""
        n_stages = self.conf.depth
        n_micro = self.n_micro
        mesh = self.mesh
        stage_fns = self._stage_fns
        ticks = n_micro + n_stages - 1
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def local(micro_loc):
            sid = jax.lax.axis_index("stage")
            act_shape = micro_loc.shape[1:]
            with jax.named_scope("shisha.init"):
                buf = jnp.zeros(act_shape, micro_loc.dtype)
                outs = jnp.zeros((n_micro,) + act_shape, micro_loc.dtype)

            def tick(carry, t):
                buf, outs = carry
                # stage 0 ingests microbatch t (when in range)
                with jax.named_scope("shisha.inject"):
                    take = jnp.clip(t, 0, n_micro - 1)
                    inject = micro_loc[take]
                    x = jnp.where(sid == 0, jnp.where(t < n_micro, inject, buf * 0), buf)
                y = jax.lax.switch(sid, stage_fns, x)
                # last stage emits microbatch t - (n_stages - 1)
                with jax.named_scope("shisha.emit"):
                    emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
                    valid = (t - (n_stages - 1) >= 0) & (sid == n_stages - 1)
                    outs = jax.lax.dynamic_update_index_in_dim(
                        outs, jnp.where(valid, y, outs[emit_idx]), emit_idx, 0
                    )
                # ship activations one stage forward
                with jax.named_scope("shisha.ship"):
                    buf = jax.lax.ppermute(y, "stage", fwd)
                return (buf, outs), None

            (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
            # bring results from the last stage to every shard (replicated out)
            with jax.named_scope("shisha.gather"):
                outs = jax.lax.psum(
                    jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), "stage"
                )
            return outs

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=P(),  # microbatches replicated; stages own the compute
            out_specs=P(),
            check_vma=False,
        )(micro)

    def run(self, micro: jax.Array) -> jax.Array:
        """micro: [n_micro, ...]. Returns [n_micro, ...] final activations."""
        with TraceAnnotation("shisha.run"):
            return self._run(micro)

