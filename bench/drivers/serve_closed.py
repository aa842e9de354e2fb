"""Closed-loop serving: back-to-back calls of the Shisha pipeline.

One call takes one batch of host images (``n_micro`` microbatches of
``batch`` images), hands it to the stages' chips, pads it to the canonical
activation, runs ``PipelineRunner`` and brings the cropped outputs back to
the host.  The next call starts when the last one's outputs are on the host.
The cut is the Alg. 1 seed for ``stages`` stages, one stage per chip.

Traffic parameters: ``stages``, ``n_micro``, ``batch``, ``pool`` (distinct
seeded input batches, used in turn) and ``sample`` (calls kept for the check).
"""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import generate_seed, weights
from repro.launch.mesh import make_stage_mesh
from repro.models.cnn import canonical_pipeline_apply, network_layers
from repro.pipeline import PipelineRunner
from repro.pipeline.hetero import tpu_platform_from_mesh

from .. import harness, reference, work

# the modelled platform whose Alg. 1 seed cuts the chain, as the tuner sees it
SEED_PLATFORM_EPS = 4


def drive(run: harness.Run) -> harness.Outcome:
    cfg, tr = run.config, run.traffic
    rows = reference.layer_rows(cfg)
    names = [r["name"] for r in rows]
    n_stages, n_micro, batch = tr["stages"], tr["n_micro"], tr["batch"]
    in_shape = (cfg["input_hw"], cfg["input_hw"], cfg["input_channels"])

    model = harness.program_model(cfg)
    params = harness.he_weights(rows, run.seed, run.devices[0])
    run.mark("weights")
    platform = tpu_platform_from_mesh(max(SEED_PLATFORM_EPS, n_stages), chips_per_stage=1)
    conf = generate_seed(weights(network_layers(cfg["network"])), platform, n_stages=n_stages).conf
    mesh = make_stage_mesh(conf.depth)
    run.facts["cut"] = conf.boundaries()

    apply_fn, to_canon, crop_out, canon = canonical_pipeline_apply(model, params, in_shape)

    def scoped(i, xc):
        with jax.named_scope(names[i]):
            return apply_fn(i, xc)

    runner = PipelineRunner(mesh=mesh, conf=conf, apply_layer=scoped, n_micro=n_micro)
    replicated = NamedSharding(mesh, P())
    pad = jax.jit(jax.vmap(to_canon), out_shardings=replicated)
    crop = jax.jit(crop_out)

    rng = harness.host_rng(run.seed, harness.STREAM_IMAGES)
    pool = [rng.standard_normal((n_micro, batch, *in_shape), dtype=np.float32) for _ in range(tr["pool"])]
    run.mark("inputs")

    def call(x: np.ndarray) -> np.ndarray:
        with TraceAnnotation("h2d"):
            xd = jax.device_put(x, replicated)
        with TraceAnnotation("pad"):
            micro = pad(xd)
        with TraceAnnotation("call"):
            out = runner.run(micro)
        with TraceAnnotation("crop_d2h"):
            return np.asarray(crop(out))

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for x in pool[:2]:
            call(x)  # compiles, then runs once more warm
        run.mark("warm")

        sample = harness.Reservoir(tr["sample"], run.seed)
        traced = harness.TracedWindow(run)
        latencies = []
        traced.start()
        t_start = run.start_window()
        t_stop = t_start + run.seconds
        while time.perf_counter() < t_stop:
            k = len(latencies) % len(pool)
            t0 = time.perf_counter()
            got = call(pool[k])
            latencies.append(time.perf_counter() - t0)
            sample.offer(lambda k=k, got=got: (k, got))
            traced.tick()
        traced.stop()
        run.end_window()
        run.read_peak_bytes()
        # the layers' op name paths, from the very program the window ran
        micro = jax.ShapeDtypeStruct((n_micro, batch, *canon), np.float32, sharding=replicated)
        hlo = [runner._run.lower(micro).compile().as_text()] if traced.on else []

    del runner, pad, crop, apply_fn, scoped
    reduction = traced.reduce(names, len(run.devices), hlo)
    peak = work.peaks(run.devices[0].device_kind)
    calls = len(latencies)
    run.facts.update(
        calls=calls,
        images=calls * n_micro * batch,
        traced_calls=traced.units,
        traced_s=traced.seconds,
        trace=reduction,
        peak=peak,
        flops_per_call=n_micro * work.total_flops(rows, batch),
        least_s_per_call=n_micro * sum(work.least_seconds(r, peak, batch, cfg["dtype"]) for r in rows),
    )

    # the check: every sampled answer against the plain reference, on its inputs
    ref = reference.make_forward(rows, cfg["matmul_precision"])
    errs = []
    for k, got in sample.items:
        x = pool[k]
        want = np.asarray(ref(params, x.reshape(-1, *in_shape)))
        want = want.reshape(n_micro, batch, *want.shape[1:])
        errs.append(reference.rel_err(got, want))
    limit = cfg["limits"]["serve_rel_err"]
    return harness.Outcome(
        attempted=calls,
        failed=sum(e > limit for e in errs),
        end_to_end={
            "images_per_s": run.facts["images"] / run.facts["window_s"],
            "call_p90_ms": 1e3 * float(np.quantile(latencies, 0.9)),
        },
        checks={"serve_rel_err": (max(errs), limit)},
    )
