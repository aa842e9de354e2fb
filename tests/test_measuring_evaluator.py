"""``MeasuringEvaluator``'s compiled layer programs and its one sync a layer.

The first evaluator over a layer function compiles it and warms it (a miss:
two syncs); a later one over the same function, argument shapes and matmul
precision finds it compiled (a hit: one sync).  A new precision or shape is
a miss again, a dropped function leaves the cache, and every evaluator
measures every layer itself.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import paper_platform
from repro.models.cnn import make_cnn, network_layers
from repro.pipeline import MeasuringEvaluator
from repro.pipeline import runtime

N = 3


@pytest.fixture(scope="module")
def chain():
    """The first N scaled synthnet layers: their params and real inputs."""
    model = make_cnn("synthnet", scale=0.1)
    params = model.init(jax.random.PRNGKey(0))
    first = model.specs[0]
    hw = first.h_out * first.stride
    xs, x = [], jax.random.normal(jax.random.PRNGKey(1), (1, hw, hw, first.c_in))
    for i in range(N):
        xs.append(x)
        x = model.apply_layer(i, params[i], x)
    return model, params[:N], xs


def fresh_fns(model):
    return [lambda p, x, i=i: model.apply_layer(i, p, x) for i in range(N)]


def evaluator(fns, args, reps=2):
    return MeasuringEvaluator(paper_platform(2), network_layers("synthnet")[:N], layer_fns=fns, layer_args=args, reps=reps)


def test_a_miss_syncs_twice_a_hit_once(chain):
    model, params, xs = chain
    fns, args = fresh_fns(model), list(zip(params, xs))
    first, second = evaluator(fns, args), evaluator(fns, args)
    assert (first.program_misses, first.program_hits, first.syncs) == (N, 0, 2 * N)
    assert (second.program_misses, second.program_hits, second.syncs) == (0, N, N)


@pytest.mark.parametrize("reps", [1, 3])
def test_every_evaluator_measures_every_layer(chain, reps):
    model, params, xs = chain
    fns, args = fresh_fns(model), list(zip(params, xs))
    evs = [evaluator(fns, args, reps=reps) for _ in range(3)]
    for ev in evs:
        assert len(ev._measured) == N and all(t > 0 for t in ev._measured)
    assert [ev.program_hits for ev in evs] == [0, N, N]


@pytest.mark.parametrize("change", ["precision", "shape"])
def test_new_precision_or_shape_is_a_miss(chain, change):
    model, params, xs = chain
    fns, args = fresh_fns(model), list(zip(params, xs))
    with jax.default_matmul_precision("highest"):
        evaluator(fns, args)
    if change == "shape":
        args = [(p, jnp.concatenate([x, 2 * x])) for p, x in args]  # batch 2
    precision = "bfloat16" if change == "precision" else "highest"
    with jax.default_matmul_precision(precision):
        ev = evaluator(fns, args)
        assert (ev.program_misses, ev.program_hits, ev.syncs) == (N, 0, 2 * N)
        again = evaluator(fns, args)
        assert (again.program_misses, again.program_hits) == (0, N)
        for fn, a in zip(fns, args):
            programs = runtime._PROGRAMS[fn]
            assert len(programs) == 2
            program = programs[runtime._program_key(a)]
            np.testing.assert_array_equal(np.asarray(program(*a)), np.asarray(jax.jit(fn)(*a)))


def test_a_dropped_function_leaves_the_cache(chain):
    model, params, xs = chain
    fns, args = fresh_fns(model), list(zip(params, xs))
    ev = evaluator(fns, args)
    refs = [weakref.ref(fn) for fn in fns]
    assert all(r() in runtime._PROGRAMS for r in refs)
    before = len(runtime._PROGRAMS)
    del ev, fns
    gc.collect()
    assert all(r() is None for r in refs)
    assert len(runtime._PROGRAMS) == before - N
