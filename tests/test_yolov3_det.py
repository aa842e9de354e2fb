"""YOLOv3 as published (``yolov3_det``): Darknet-53 with its shortcut adds and
the three detection heads, a layer graph carried through the stage pipeline
as a live set in the flat canonical.

At full width, from the spec table and abstract params: the layer count, the
work and weights, the live sets and the canonical's size.  At a small size on
the CPU: the pipeline against the sequential model, bit for bit, on one stage
and on four host devices with cuts that a residual block and the 52-scale
route cross; the live-set prices the evaluators read; and the tuner's path
from the Alg. 1 seed through ``MeasuringEvaluator`` to ``PipelineRunner``.
"""

import math

import jax
import numpy as np
import pytest

from repro.core import Trace, generate_seed, paper_platform, run_shisha, weights
from repro.launch.mesh import make_stage_mesh
from repro.models.cnn import (
    canonical_pipeline_apply,
    live_sets,
    live_slots,
    make_cnn,
    network_layers,
    out_shape,
    reads,
    tensor_shape,
    yolov3_det_specs,
)
from repro.pipeline import MeasuringEvaluator, PipelineRunner

from test_program_spans import on_host_devices

IN_FULL = (416, 416, 3)
SCALE, IN_SMALL = 0.05, (64, 64, 3)  # grids 64 ... 2, every stride aligned
# cuts after res128_0_1x1 (inside a residual block), after res256_7_3x3 and
# after h26_0 (both while res256_7_3x3 waits for the 52-scale route)
FOUR_STAGES = (6, 20, 35, 14)


def index(specs, name):
    return next(i for i, sp in enumerate(specs) if sp.name == name)


def test_full_width_work_and_weights():
    specs = yolov3_det_specs()
    layers = network_layers("yolov3_det")
    assert len(specs) == len(layers) == 75
    assert sum(l.flops for l in layers) == 65_864_075_264
    assert sum(l.flops for l in layers[index(specs, "h13_0") :]) == 16_832_464_896
    assert sum(sp.r * sp.s * sp.c_in * sp.k + sp.k for sp in specs) == 61_922_845
    assert sum(sp.add is not None for sp in specs) == 23
    assert [sp.c_in for sp in specs if sp.srcs and len(sp.srcs) == 2] == [768, 384]
    assert [out_shape(specs[i]) for i in (58, 66, 74)] == [(13, 13, 255), (26, 26, 255), (52, 52, 255)]
    assert [specs[i].name for i in (58, 66, 74)] == ["h13_out", "h26_out", "h52_out"]


def test_full_width_live_sets_and_canonical():
    specs = yolov3_det_specs()
    model = make_cnn("yolov3_det")
    live = live_sets(specs)
    assert len(live) == 76 and sum(len(ts) > 1 for ts in live) == 60
    lifetimes = {name: sum(index(specs, name) in ts for ts in live) for name in ("res256_7_3x3", "res512_7_3x3", "h13_out")}
    assert lifetimes == {"res256_7_3x3": 43, "res512_7_3x3": 18, "h13_out": 17}
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    *_, canon = canonical_pipeline_apply(model, params, IN_FULL)
    largest = max(sum(math.prod(tensor_shape(specs, t)) for t in ts) for ts in live)
    assert largest == 5_537_792 and canon == (43_264, 128)  # conv0's output, and the backbone chain's canonical


def test_live_slots_never_overlap():
    specs = yolov3_det_specs()
    slots = live_slots(specs, IN_FULL)
    *_, canon = canonical_pipeline_apply(make_cnn("yolov3_det"), None, IN_FULL)
    for ts in live_sets(specs):
        rows = sorted(slots[t] for t in ts)
        assert all(a + n <= b for (a, n), (b, _) in zip(rows, rows[1:])), ts
        assert rows[-1][0] + rows[-1][1] <= canon[0]
    assert all(slots[t][1] == -(-math.prod(tensor_shape(specs, t)) // 128) for t in slots)


PIPELINE_VS_SEQUENTIAL = """
    import jax, numpy as np
    from repro.core import PipelineConfig
    from repro.launch.mesh import make_stage_mesh
    from repro.models.cnn import canonical_pipeline_apply, make_cnn
    from repro.pipeline import PipelineRunner

    model = make_cnn("yolov3_det", scale={scale})
    params = model.init(jax.random.PRNGKey(0))
    in_shape = {in_shape}
    stages = {stages}
    conf = PipelineConfig(stages=stages, eps=tuple(range(len(stages))))
    apply_fn, to_canon, crop_out, _ = canonical_pipeline_apply(model, params, in_shape)
    runner = PipelineRunner(mesh=make_stage_mesh(len(stages)), conf=conf, apply_layer=apply_fn, n_micro=4)
    raw = jax.random.normal(jax.random.PRNGKey(1), (4, 1) + in_shape)
    outs = jax.jit(crop_out)(runner.run(jax.vmap(to_canon)(raw)))
    refs = jax.jit(jax.vmap(model, in_axes=(None, 0)))(params, raw)
    print(len(outs) == 3 and all(np.array_equal(np.asarray(o), np.asarray(r)) for o, r in zip(outs, refs)))
    """


@pytest.mark.parametrize("stages", [(75,), FOUR_STAGES])
def test_pipeline_bit_identical_to_sequential(stages):
    code = PIPELINE_VS_SEQUENTIAL.format(scale=SCALE, in_shape=IN_SMALL, stages=stages)
    assert on_host_devices(len(stages), code) == "True"


def test_cuts_priced_by_their_live_sets():
    full = yolov3_det_specs()
    layers = network_layers("yolov3_det")
    cuts = np.cumsum(FOUR_STAGES)[:-1]
    assert [full[b - 1].name for b in cuts] == ["res128_0_1x1", "res256_7_3x3", "h26_0"]
    crossing = [[full[t].name for t in live_sets(full)[b]] for b in cuts]
    assert crossing == [["down128", "res128_0_1x1"], ["res256_7_3x3"], ["res256_7_3x3", "h13_out", "h26_0"]]
    for b in cuts:
        assert layers[b - 1].act_bytes == 4 * sum(math.prod(out_shape(full[t])) for t in live_sets(full)[b])
    assert layers[cuts[0] - 1].act_bytes == 4 * (104 * 104 * 128 + 104 * 104 * 64)
    # the chain's price is its layer's output, as before
    r50 = make_cnn("resnet50").specs
    assert [l.act_bytes for l in network_layers("resnet50")] == [4 * sp.h_out * sp.w_out * sp.k for sp in r50]


def test_seed_measure_tune_run():
    """Alg. 1 seed, every layer (two-input ones among them) timed by
    MeasuringEvaluator at its real inputs, Alg. 2, then PipelineRunner on the
    tuned cut, against the sequential model."""
    model = make_cnn("yolov3_det", scale=SCALE)
    specs = model.specs
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *IN_SMALL))
    acts = {-1: x}
    for i in range(len(specs)):
        acts[i] = model.apply_layer(i, params[i], *(acts[t] for t in reads(specs, i)))
    layer_args = [(params[i], *(acts[t] for t in reads(specs, i))) for i in range(len(specs))]
    assert sum(len(a) == 3 for a in layer_args) == 23 + 2  # shortcut adds and route concats
    layer_fns = [lambda p, *xs, i=i: model.apply_layer(i, p, *xs) for i in range(len(specs))]
    layers = network_layers("yolov3_det")
    platform = paper_platform(2)
    seed = generate_seed(weights(layers), platform, n_stages=1)
    ev = MeasuringEvaluator(platform, layers, layer_fns=layer_fns, layer_args=layer_args, reps=1)
    assert len(ev._measured) == 75 and all(t > 0 for t in ev._measured)
    conf = run_shisha(weights(layers), Trace(ev), "H3", n_stages=1).result.best_conf
    assert conf.n_layers == 75 and conf.depth == seed.conf.depth == 1
    apply_fn, to_canon, crop_out, _ = canonical_pipeline_apply(model, params, IN_SMALL)
    runner = PipelineRunner(mesh=make_stage_mesh(1), conf=conf, apply_layer=apply_fn, n_micro=1)
    outs = crop_out(runner.run(to_canon(x)[None]))
    for o, i in zip(outs, (58, 66, 74)):
        assert np.array_equal(np.asarray(o[0]), np.asarray(acts[i]))
