"""Run Shisha's main path on a TPU, end to end, at full width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four pipeline stages on a four-chip host

The default run drives ResNet-50 at 224x224, with random weights made from
``--seed``, through the entry points a user calls, in one process:

  1. measured online tuning: ``MeasuringEvaluator`` times each of the 50
     layers on the chip at its real input shape, and ``run_shisha`` (Alg. 1
     seed + Alg. 2 tune, heuristic H3) picks a 4-stage schedule for the
     modelled heterogeneous platform ``tpu_platform_from_mesh(4)``;
  2. a 1-stage ``PipelineRunner`` warms up once, then answers 3 calls of 8
     microbatches of 1 image each on fresh seeded inputs;
  3. every answer is compared with the plain sequential model on the same
     chip, both under ``jax.default_matmul_precision("highest")``.

``--chips 4`` runs only the 4-stage ``PipelineRunner`` (one stage per chip,
schedule from the host-side seed, no tuning) and its comparison with the
sequential model on device 0.

The times and bytes printed here are smoke output, not metrics.  Without a
TPU the script exits non-zero and prints no ``ok`` line.  The last line of a
run that passed is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import Trace, generate_seed, run_shisha, weights  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_stage_mesh  # noqa: E402
from repro.models.cnn import canonical_pipeline_apply, make_cnn, network_layers  # noqa: E402
from repro.pipeline import MeasuringEvaluator, PipelineRunner  # noqa: E402
from repro.pipeline.hetero import tpu_platform_from_mesh  # noqa: E402

PLATFORM = "tpu"
NETWORK = "resnet50"
SCALE = 1.0  # channel/spatial scale of make_cnn; 1.0 is the published width
IN_HW = 224
N_MICRO = 8
BATCH = 1  # images per microbatch
N_CALLS = 3
TUNE_STAGES = 4
# max |pipeline - sequential| over max |sequential|.  Both run the same f32
# layers at "highest" matmul precision; rounding of sums of up to 4,608
# products over 50 layers stays orders of magnitude below this.
RTOL = 1e-3

# XLA compilation, persistent-cache reads included (tracing is not cached)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds of XLA compilation in this process, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


def require_devices(n_chips: int) -> list:
    devices = jax.devices()
    d = devices[0]
    print(f"[device] {devices}")
    print(f"[device] platform={d.platform} kind={d.device_kind} count={len(devices)}")
    if d.platform != PLATFORM:
        sys.exit(f"chip_smoke: needs a {PLATFORM} device, JAX found {d.platform}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, JAX found {len(devices)}")
    return devices


def tune(model, params, in_shape, platform, seed: int, clock: CompileClock):
    """Alg. 1 + Alg. 2 against per-layer times measured on the chip."""
    layers = network_layers(NETWORK)
    # layer i takes layer i-1's output; MeasuringEvaluator times it at that shape
    shapes = [(BATCH, *in_shape)] + [(BATCH, sp.h_out, sp.w_out, sp.k) for sp in model.specs[:-1]]
    key = jax.random.PRNGKey(seed + 2)
    layer_args = [(jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32),) for i, s in enumerate(shapes)]
    layer_fns = [functools.partial(model.apply_layer, i, params[i]) for i in range(len(model.specs))]
    c0, t0 = clock.seconds, time.perf_counter()
    ev = MeasuringEvaluator(platform, layers, layer_fns=layer_fns, layer_args=layer_args)
    measure_s = time.perf_counter() - t0
    trace = Trace(ev)
    res = run_shisha(weights(layers), trace, "H3", n_stages=TUNE_STAGES)
    conf = res.result.best_conf
    print(f"[tune] schedule {conf.pretty([ep.name for ep in platform.eps])}  {conf}")
    print(
        f"[tune] {trace.n_trials} trials; measuring {len(layer_fns)} layers took {measure_s:.3f} s "
        f"(compile {clock.seconds - c0:.3f} s); modelled throughput {res.result.best_throughput:.3f}/s"
    )
    return conf


def serve_and_check(model, params, in_shape, mesh, conf, seed: int, clock: CompileClock) -> None:
    """Warm up, answer N_CALLS calls, and compare each with the sequential model."""
    apply_fn, to_canon, crop_out, canon = canonical_pipeline_apply(model, params, in_shape)
    runner = PipelineRunner(mesh=mesh, conf=conf, apply_layer=apply_fn, n_micro=N_MICRO)
    stage_devices = list(mesh.devices[:, 0])
    for s, (d, (a, b)) in enumerate(zip(stage_devices, conf.boundaries())):
        print(f"[pipeline] stage {s}: layers [{a}, {b}) on device id={d.id} coords={getattr(d, 'coords', None)}")
    if len({d.id for d in stage_devices}) != len(stage_devices):
        sys.exit(f"chip_smoke: stages share a device: {[d.id for d in stage_devices]}")
    print(f"[pipeline] {conf.depth} stage(s), n_micro={N_MICRO}, {BATCH} image(s) per microbatch, canonical activation {canon}")

    replicated = NamedSharding(mesh, P())
    canonical = jax.jit(jax.vmap(to_canon), out_shardings=replicated)
    sequential = jax.jit(model.__call__)
    key = jax.random.PRNGKey(seed + 1)

    def answer(i: int):
        raw = jax.random.normal(jax.random.fold_in(key, i), (N_MICRO, BATCH, *in_shape), jnp.float32)
        micro = jax.block_until_ready(canonical(raw))
        c0, t0 = clock.seconds, time.perf_counter()
        out = jax.block_until_ready(runner.run(micro))
        seconds = time.perf_counter() - t0
        got = np.asarray(crop_out(out))
        del micro, out  # each holds N_MICRO canonical activations on every stage
        return raw, got, seconds, clock.seconds - c0

    with jax.default_matmul_precision("highest"):
        _, _, warm_s, warm_compile_s = answer(0)
        print(f"[smoke] warm-up call {warm_s:.3f} s, of which compile {warm_compile_s:.3f} s")
        for i in range(1, N_CALLS + 1):
            raw, got, seconds, compile_s = answer(i)
            if compile_s:
                sys.exit(f"chip_smoke: call {i} compiled again ({compile_s:.3f} s)")
            want = np.stack([np.asarray(sequential(params, raw[m])) for m in range(N_MICRO)])
            if got.shape != want.shape or not np.all(np.isfinite(got)):
                sys.exit(f"chip_smoke: call {i} gave shape {got.shape} (want {want.shape}) or non-finite values")
            scale = float(np.max(np.abs(want)))
            abs_err = float(np.max(np.abs(got - want)))
            rel_err = abs_err / scale if scale > 0 else float("inf")
            print(
                f"[smoke] call {i}: {seconds:.6f} s, output {got.shape}, max|ref| {scale:.6e}, "
                f"max abs err {abs_err:.6e}, max rel err {rel_err:.6e} (tolerance {RTOL:g})"
            )
            if not rel_err <= RTOL:
                sys.exit(f"chip_smoke: call {i} differs from the sequential reference: rel err {rel_err:.3e} > {RTOL:g}")
    for d in stage_devices:
        stats = d.memory_stats() or {}
        print(f"[smoke] device id={d.id} peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_devices(args.chips)
    print(f"[cache] {enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        model = make_cnn(NETWORK, scale=SCALE)
        params = model.init(jax.random.PRNGKey(args.seed))
        in_shape = (IN_HW, IN_HW, 3)
        print(f"[model] {NETWORK} scale={SCALE} input {in_shape}, {len(model.specs)} layers, seed={args.seed}")
        platform = tpu_platform_from_mesh(TUNE_STAGES, chips_per_stage=1)
        if args.chips == 1:
            tune(model, params, in_shape, platform, args.seed, clock)
        # one stage per chip, cut by the host-side seed (Alg. 1)
        conf = generate_seed(weights(network_layers(NETWORK)), platform, n_stages=args.chips).conf
        serve_and_check(model, params, in_shape, make_stage_mesh(conf.depth), conf, args.seed, clock)
        print(f"[smoke] compile total {clock.seconds:.3f} s")
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
