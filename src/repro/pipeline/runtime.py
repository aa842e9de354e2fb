"""Shisha-scheduled pipeline runtime.

The paper's deployment story, on JAX: a chain-structured network (the
paper's CNNs, or any LM block stack) is split into N contiguous stages by
a Shisha ``PipelineConfig``; each stage is pinned to one slice of the mesh
("stage" axis = the chiplet axis) and microbatches stream through the
stages with ``jax.lax.ppermute`` — GPipe-style fill/steady/drain, built
with shard_map so every transfer is an explicit neighbour permute (the
paper's inter-chiplet link).

:class:`MeasuringEvaluator` closes the online-tuning loop: it times each
(layer, EP) pair on the real device (jitted, synced) and scales by the EP
derate (hetero.py).  This is the paper's "runtime performance value" —
Algorithm 2 consumes it exactly like the gem5 database.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from ..core.config import PipelineConfig
from ..core.evaluator import AnalyticEvaluator
from .hetero import EPDerates

# ---------------------------------------------------------------------------
# Measured oracle
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MeasuringEvaluator(AnalyticEvaluator):
    """`execute(conf)` backed by real measured per-layer times.

    Each layer's apply function is jitted and timed once per EP class
    (block_until_ready, best of ``reps``); a layer's time on an EP is its
    measured time scaled by that EP's derate — the live analogue of the
    paper's gem5 database.  Stage times, throughput and the link cost come
    from AnalyticEvaluator over that table.
    """

    layer_fns: Sequence[Callable] | None = None
    layer_args: Sequence[tuple] | None = None
    reps: int = 3

    def __post_init__(self):
        self.derates = EPDerates.from_platform(self.platform)
        self._measured: list[float] = []
        # profiler spans around, never inside, the timed calls: with the
        # profiler off each costs about a microsecond
        with TraceAnnotation("shisha.measure"):
            for i, (fn, args) in enumerate(zip(self.layer_fns, self.layer_args)):
                with TraceAnnotation("shisha.measure.warm", layer=i):
                    jf = jax.jit(fn)
                    out = jf(*args)
                    jax.block_until_ready(out)  # compile + warm
                best = np.inf
                with TraceAnnotation("shisha.measure.timed", layer=i, calls=self.reps):
                    for _ in range(self.reps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(jf(*args))
                        best = min(best, time.perf_counter() - t0)
                self._measured.append(best)

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

