"""Records ``v5e_program.xplane.pb`` and ``v5e_program.hlo.txt`` on one TPU chip.

    python3 bench/testdata/record_v5e_program.py --out <dir>

The trace holds what ``test_bench_program_trace.py`` reads: three calls of
a two-layer, one-stage ``PipelineRunner`` (each in the serve driver's
``call`` and ``crop_d2h`` spans), then one two-layer ``MeasuringEvaluator``
build in a ``measure`` span.  Layers are scoped ``layerA`` and ``layerB`` as
the harness scopes them.  The HLO file holds the compiled text of the runner,
the crop and both layer programs, one after another.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.core import PipelineConfig, paper_platform  # noqa: E402
from repro.launch.mesh import make_stage_mesh  # noqa: E402
from repro.models.cnn import CNNModel, ConvSpec, _to_layers, canonical_pipeline_apply  # noqa: E402
from repro.pipeline import MeasuringEvaluator, PipelineRunner  # noqa: E402

NAMES = ("layerA", "layerB")
SPECS = (ConvSpec("layerA", 56, 56, 64, 3, 3, 64), ConvSpec("layerB", 56, 56, 64, 1, 1, 64))
IN_SHAPE = (56, 56, 64)
CALLS = 3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    out = Path(ap.parse_args().out)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_v5e_program: needs a tpu device")
    model = CNNModel(specs=SPECS)
    params = model.init(jax.random.PRNGKey(0))
    apply_fn, to_canon, crop_out, canon = canonical_pipeline_apply(model, params, IN_SHAPE)

    def scoped(i, xc):
        with jax.named_scope(NAMES[i]):
            return apply_fn(i, xc)

    conf = PipelineConfig(stages=(2,), eps=(0,))
    runner = PipelineRunner(mesh=make_stage_mesh(1), conf=conf, apply_layer=scoped, n_micro=2)
    crop = jax.jit(crop_out)
    micro = jax.vmap(to_canon)(jax.random.normal(jax.random.PRNGKey(1), (2, 1, *IN_SHAPE)))

    def layer_fn(i):
        def fn(p, x):
            with jax.named_scope(NAMES[i]):
                return model.apply_layer(i, p, x)

        return fn

    layer_fns = [layer_fn(i) for i in range(len(SPECS))]
    layer_args = [(p, jax.random.normal(jax.random.PRNGKey(2), (1, *IN_SHAPE))) for p in params]
    platform, layers = paper_platform(2), _to_layers(SPECS)

    def measure():
        return MeasuringEvaluator(platform, layers, layer_fns=layer_fns, layer_args=layer_args)

    np.asarray(crop(runner.run(micro)))  # compiles
    measure()  # compiles the layer programs

    trace_dir = tempfile.mkdtemp(prefix="record_v5e_program_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(CALLS):
        with TraceAnnotation("call"):
            y = runner.run(micro)
        with TraceAnnotation("crop_d2h"):
            np.asarray(crop(y))
    with TraceAnnotation("measure"):
        measure()
    jax.profiler.stop_trace()

    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(sorted(Path(trace_dir).glob("**/*.xplane.pb"))[-1], out / "v5e_program.xplane.pb")
    texts = [runner._run.lower(micro).compile().as_text(), crop.lower(micro).compile().as_text()]
    texts += [jax.jit(fn).lower(*args).compile().as_text() for fn, args in zip(layer_fns, layer_args)]
    (out / "v5e_program.hlo.txt").write_text("\n".join(texts))
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"recorded {out / 'v5e_program.xplane.pb'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
