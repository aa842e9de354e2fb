"""The control of the check: the plain reference one precision step below the
configuration's, put in the program's place, must come out as not correct.

    python3 -m bench.control --workload resnet50.serve.1chip --seeds 1 2 3

For each seed it builds what a run of the cell builds (weights, and the
inputs the check compares: the serving cell's first ``sample`` input
batches, or the tuning cell's layer inputs), at the cell's own size, and
prints the number the cell compares when the answers come from the
reference in three bfloat16 passes ("high") instead of from the program,
beside the cell's limit.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from . import harness, reference  # noqa: E402
from .drivers import tune_repeat  # noqa: E402

CONTROL_PRECISION = "high"


def serve_reading(config: dict, traffic: dict, seed: int, device) -> float:
    rows = reference.layer_rows(config)
    in_shape = (config["input_hw"], config["input_hw"], config["input_channels"])
    params = harness.he_weights(rows, seed, device)
    rng = harness.host_rng(seed, harness.STREAM_IMAGES)
    n = traffic["n_micro"] * traffic["batch"]
    batches = [rng.standard_normal((n, *in_shape), dtype=np.float32) for _ in range(traffic["pool"])]
    want = reference.make_forward(rows, config["matmul_precision"])
    got = reference.make_forward(rows, CONTROL_PRECISION)
    return max(reference.rel_err(np.asarray(got(params, x)), np.asarray(want(params, x))) for x in batches[: traffic["sample"]])


def tune_reading(config: dict, traffic: dict, seed: int, device) -> float:
    rows = reference.layer_rows(config)
    in_shape = (config["input_hw"], config["input_hw"], config["input_channels"])
    params = harness.he_weights(rows, seed, device)
    b = traffic["batch"]
    shapes = [(b, *in_shape)] + [(b, r["h_out"], r["w_out"], r["k"]) for r in rows[:-1]]
    xs = tune_repeat.layer_inputs(shapes, seed, device)

    def layers(precision):
        return jax.jit(lambda ps, xs: [reference.layer(r, p, x, precision) for r, p, x in zip(rows, ps, xs)])

    want = layers(config["matmul_precision"])(params, xs)
    got = layers(CONTROL_PRECISION)(params, xs)
    return max(reference.rel_err(np.asarray(g), np.asarray(w)) for g, w in zip(got, want))


READINGS = {"serve_closed": ("serve_rel_err", serve_reading), "tune_repeat": ("layer_rel_err", tune_reading)}


def main(argv: list[str] | None = None) -> int:
    from . import run

    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    devices = run.require_devices(spec["cell"]["chips"])
    run.use_checkout_cache()
    name, reading = READINGS[spec["traffic"]["driver"]]
    limit = spec["config"]["limits"][name]
    for seed in args.seeds:
        value = reading(spec["config"], spec["traffic"], seed, devices[0])
        print(json.dumps({"workload": args.workload, "seed": seed, "control": name, "value": value, "limit": limit,
                          "fails": value > limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
