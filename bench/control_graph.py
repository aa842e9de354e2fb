"""The control of a layer graph's check: ``reference_graph`` one precision
step below the configuration's, put in the program's place, must come out
as not correct.

    python3 -m bench.control_graph --workload yolov3_det.serve.1chip --seeds 1 2 3

As ``bench.control`` does for the chain cells: for each seed it builds the
weights and the cell's first ``sample`` input batches, at the cell's own
size, and prints ``serve_rel_err`` (the widest over the network's outputs)
when the answers come from the reference in three bfloat16 passes ("high")
instead of from the program, beside the cell's limit.  The benchmark's own
runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from . import harness, reference_graph  # noqa: E402
from .control import CONTROL_PRECISION  # noqa: E402


def serve_reading(config: dict, traffic: dict, seed: int, device, want, got) -> float:
    """``want``, ``got``: ``reference_graph.make_forward`` of the configuration
    at its own precision and at the control's, made once for every seed."""
    rows = reference_graph.graph_rows(config)
    in_shape = (config["input_hw"], config["input_hw"], config["input_channels"])
    params = harness.he_weights(rows, seed, device)
    rng = harness.host_rng(seed, harness.STREAM_IMAGES)
    n = traffic["n_micro"] * traffic["batch"]
    batches = [rng.standard_normal((n, *in_shape), dtype=np.float32) for _ in range(traffic["pool"])]
    return max(reference_graph.rel_err(got(params, x), want(params, x)) for x in batches[: traffic["sample"]])


def main(argv: list[str] | None = None) -> int:
    from . import run

    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    devices = run.require_devices(spec["cell"]["chips"])
    run.use_checkout_cache()
    config = spec["config"]
    limit = config["limits"]["serve_rel_err"]
    rows = reference_graph.graph_rows(config)
    want = reference_graph.make_forward(rows, config["matmul_precision"])
    got = reference_graph.make_forward(rows, CONTROL_PRECISION)
    for seed in args.seeds:
        value = serve_reading(config, spec["traffic"], seed, devices[0], want, got)
        print(json.dumps({"workload": args.workload, "seed": seed, "control": "serve_rel_err", "value": value,
                          "limit": limit, "fails": value > limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
