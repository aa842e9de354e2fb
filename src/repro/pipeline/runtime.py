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
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import PipelineConfig
from ..core.cost_model import Layer
from ..core.evaluator import AnalyticEvaluator
from ..core.platform import Platform
from .hetero import EPDerates

# ---------------------------------------------------------------------------
# Measured oracle
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MeasuringEvaluator(AnalyticEvaluator):
    """`execute(conf)` backed by real measured per-layer times.

    Each layer's apply function is jitted and timed once per EP class
    (block_until_ready, best of ``reps``); stage times are sums of measured
    layer times scaled by the stage EP's derate — the live analogue of the
    paper's gem5 database.  Inherits stage_times/throughput plumbing (link
    cost model included) from AnalyticEvaluator.
    """

    layer_fns: Sequence[Callable] | None = None
    layer_args: Sequence[tuple] | None = None
    reps: int = 3

    def __post_init__(self):
        self.derates = EPDerates.from_platform(self.platform)
        self._measured: list[float] = []
        for fn, args in zip(self.layer_fns, self.layer_args):
            jf = jax.jit(fn)
            out = jf(*args)
            jax.block_until_ready(out)  # compile + warm
            best = np.inf
            for _ in range(self.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(jf(*args))
                best = min(best, time.perf_counter() - t0)
            self._measured.append(best)

    def layer_time(self, layer: Layer, ep_idx: int) -> float:  # type: ignore[override]
        li = list(self.layers).index(layer)
        return self.derates.scale(ep_idx, self._measured[li]) + self.layer_overhead

    def stage_times(self, conf: PipelineConfig) -> list[float]:
        times = []
        for s, (a, b) in enumerate(conf.boundaries()):
            ep_idx = conf.eps[s]
            t = sum(self.derates.scale(ep_idx, self._measured[i]) + self.layer_overhead for i in range(a, b))
            if s < conf.depth - 1:
                ep = self.platform.eps[ep_idx]
                nxt = self.platform.eps[conf.eps[s + 1]]
                t += self.layers[b - 1].act_bytes / min(ep.link_bw, nxt.link_bw) + max(
                    ep.link_latency, nxt.link_latency
                )
            times.append(t)
        return times


# ---------------------------------------------------------------------------
# shard_map GPipe pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PipelineRunner:
    """Runs a layer chain as an N-stage microbatched pipeline.

    ``apply_layer(i, x)`` must map a canonical activation shape to itself
    (the CNN model resizes internally; LM blocks are shape-preserving), so
    stages of different depth stay branch-compatible under lax.switch.
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
            buf = jnp.zeros(act_shape, micro_loc.dtype)
            outs = jnp.zeros((n_micro,) + act_shape, micro_loc.dtype)

            def tick(carry, t):
                buf, outs = carry
                # stage 0 ingests microbatch t (when in range)
                take = jnp.clip(t, 0, n_micro - 1)
                inject = micro_loc[take]
                x = jnp.where(sid == 0, jnp.where(t < n_micro, inject, buf * 0), buf)
                y = jax.lax.switch(sid, stage_fns, x)
                # last stage emits microbatch t - (n_stages - 1)
                emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
                valid = (t - (n_stages - 1) >= 0) & (sid == n_stages - 1)
                outs = jax.lax.dynamic_update_index_in_dim(
                    outs, jnp.where(valid, y, outs[emit_idx]), emit_idx, 0
                )
                # ship activations one stage forward
                buf = jax.lax.ppermute(y, "stage", fwd)
                return (buf, outs), None

            (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
            # bring results from the last stage to every shard (replicated out)
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
        return self._run(micro)

