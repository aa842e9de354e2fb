"""Back-to-back measured tunes: Shisha's online tuner with every layer timed
on the chip.

One tune is a new ``MeasuringEvaluator``, which jits and times each layer on
the chip at its real input shape, then ``run_shisha`` (Alg. 1 seed + Alg. 2,
heuristic ``heuristic``) cutting the chain into ``stages`` stages of the
modelled platform ``tpu_platform_from_mesh(stages, chips_per_stage=1)``.
The layer functions and their inputs are built once in set-up, as a user who
re-tunes keeps the model; each layer's weights are among its arguments.
``PipelineRunner`` is not on this path.

Traffic parameters: ``stages``, ``batch``, ``heuristic``.
"""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import Trace, run_shisha, weights
from repro.models.cnn import network_layers
from repro.pipeline import MeasuringEvaluator
from repro.pipeline.hetero import tpu_platform_from_mesh

from .. import harness, reference


def layer_inputs(shapes: list[tuple], seed: int, device) -> tuple:
    """Standard normal input of each layer, made on the device in one call."""
    key = jax.device_put(harness.jax_key(seed, harness.STREAM_LAYER_INPUTS), device)

    return jax.block_until_ready(jax.jit(lambda k: tuple(harness.normal_parts(k, shapes)))(key))


def cut_faults(conf, n_layers: int, n_stages: int, n_eps: int) -> int:
    """How far a tuned configuration is from a valid cut: contiguous stages
    that hold every layer once, at most ``n_stages`` of them, each on its own EP."""
    faults = 0
    faults += conf.n_layers != n_layers
    faults += not 1 <= conf.depth <= n_stages
    faults += any(s <= 0 for s in conf.stages)
    faults += len(set(conf.eps)) != len(conf.eps)
    faults += any(not 0 <= e < n_eps for e in conf.eps)
    return int(faults)


def drive(run: harness.Run) -> harness.Outcome:
    cfg, tr = run.config, run.traffic
    rows = reference.layer_rows(cfg)
    names = [r["name"] for r in rows]
    n_stages, batch = tr["stages"], tr["batch"]
    in_shape = (cfg["input_hw"], cfg["input_hw"], cfg["input_channels"])

    model = harness.program_model(cfg)
    params = harness.he_weights(rows, run.seed, run.devices[0])
    run.mark("weights")
    layers = network_layers(cfg["network"])
    platform = tpu_platform_from_mesh(n_stages, chips_per_stage=1)
    # layer i takes layer i-1's output; MeasuringEvaluator times it at that shape
    shapes = [(batch, *in_shape)] + [(batch, r["h_out"], r["w_out"], r["k"]) for r in rows[:-1]]
    xs = layer_inputs(shapes, run.seed, run.devices[0])
    # each layer's weights are an argument of its program, not constants in
    # it, so that the compiled programs do not depend on the seed and come
    # from the persistent cache after a cell's first run
    layer_args = [(p, x) for p, x in zip(params, xs)]
    run.mark("inputs")

    def layer_fn(i):
        def fn(p, x):
            with jax.named_scope(names[i]):
                return model.apply_layer(i, p, x)

        return fn

    layer_fns = [layer_fn(i) for i in range(len(rows))]

    def one_tune():
        t0 = time.perf_counter()
        with TraceAnnotation("measure"):
            ev = MeasuringEvaluator(platform, layers, layer_fns=layer_fns, layer_args=layer_args)
        t1 = time.perf_counter()
        with TraceAnnotation("tuner"):
            res = run_shisha(weights(layers), Trace(ev), tr["heuristic"], n_stages=n_stages)
        t2 = time.perf_counter()
        return res.result.best_conf, t1 - t0, t2 - t1

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        one_tune()  # compiles every layer program
        run.mark("warm")

        traced = harness.TracedWindow(run)
        confs, measure_s, tuner_s = [], [], []
        traced.start()
        t_stop = run.start_window() + run.seconds
        while time.perf_counter() < t_stop:
            conf, m, t = one_tune()
            confs.append(conf)
            measure_s.append(m)
            tuner_s.append(t)
            traced.tick()
        traced.stop()
        run.end_window()

        run.read_peak_bytes()
        # the outputs of the very layer programs the window timed (jit's cache
        # hands back the same compiled programs), on the inputs they were timed on
        outs = [np.asarray(jax.jit(fn)(*args)) for fn, args in zip(layer_fns, layer_args)]
        hlo = [jax.jit(fn).lower(*args).compile().as_text() for fn, args in zip(layer_fns, layer_args)] if traced.on else []

    reduction = traced.reduce(names, len(run.devices), hlo)
    tunes = len(confs)
    run.facts.update(
        tunes=tunes,
        traced_tunes=traced.units,
        traced_s=traced.seconds,
        trace=reduction,
        measure_s=measure_s,
        tuner_s=tuner_s,
        cuts=sorted({str(c.boundaries()) for c in confs}),
    )

    ref = jax.jit(lambda ps, xs: [reference.layer(r, p, x, cfg["matmul_precision"]) for r, p, x in zip(rows, ps, xs)])
    wants = [np.asarray(w) for w in ref(params, xs)]
    layer_err = max(reference.rel_err(o, w) for o, w in zip(outs, wants))
    bad_cuts = sum(cut_faults(c, len(rows), n_stages, platform.n_eps) > 0 for c in confs)
    limit = cfg["limits"]["layer_rel_err"]
    return harness.Outcome(
        attempted=tunes,
        failed=bad_cuts + (layer_err > limit),
        end_to_end={"tune_s": run.facts["window_s"] / tunes},
        checks={"layer_rel_err": (layer_err, limit), "bad_cuts": (bad_cuts, 0)},
    )
