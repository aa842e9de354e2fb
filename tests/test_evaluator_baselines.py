"""Evaluator semantics + baseline exploration algorithms."""

import math

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    AnalyticEvaluator,
    DatabaseEvaluator,
    PipelineConfig,
    Trace,
    exhaustive_search,
    generate_seed,
    hill_climbing,
    paper_platform,
    pipe_search,
    random_walk,
    run_shisha,
    simulated_annealing,
    weights,
)
from repro.models.cnn import make_cnn, network_layers
from repro.pipeline import MeasuringEvaluator
from repro.power import uniform_power


def _setup(net="synthnet", n_eps=4):
    layers = network_layers(net)
    plat = paper_platform(n_eps)
    return layers, plat


def test_throughput_is_inverse_slowest_stage():
    layers, plat = _setup()
    ev = AnalyticEvaluator(plat, layers)
    conf = PipelineConfig(stages=(9, 9), eps=(0, 1))
    ts = ev.stage_times(conf)
    assert ev.throughput(conf) == pytest.approx(1.0 / max(ts))


def test_fep_faster_than_sep():
    layers, plat = _setup()
    ev = AnalyticEvaluator(plat, layers)
    conf_fast = PipelineConfig(stages=(18,), eps=(plat.feps[0],))
    conf_slow = PipelineConfig(stages=(18,), eps=(plat.seps[0],))
    assert ev.throughput(conf_fast) > ev.throughput(conf_slow)


def test_latency_knob_inert_below_1ms():
    """Fig. 9: inter-chiplet latency only matters above ~1 ms."""
    layers, plat = _setup()
    conf = PipelineConfig(stages=(5, 5, 4, 4), eps=(0, 1, 2, 3))
    base = AnalyticEvaluator(plat, layers).throughput(conf)
    tiny = AnalyticEvaluator(plat.with_latency(1e-6), layers).throughput(conf)
    huge = AnalyticEvaluator(plat.with_latency(1.0), layers).throughput(conf)
    assert tiny == pytest.approx(base, rel=0.05)
    assert huge < 0.5 * base


def test_database_deterministic():
    layers, plat = _setup()
    ev1 = DatabaseEvaluator(plat, layers)
    ev2 = DatabaseEvaluator(plat, layers)
    conf = PipelineConfig(stages=(10, 8), eps=(0, 2))
    assert ev1.throughput(conf) == ev2.throughput(conf)


def _evaluator(cls, plat, layers):
    if cls is not MeasuringEvaluator:
        return cls(plat, layers)
    # time the first scaled synthnet layers on the host device
    model = make_cnn("synthnet", scale=0.1)
    params = model.init(jax.random.PRNGKey(0))
    first = model.specs[0]
    hw = first.h_out * first.stride
    xs, x = [], jnp.ones((1, hw, hw, first.c_in))
    for i in range(len(layers)):
        xs.append(x)
        x = model.apply_layer(i, params[i], x)
    fns = [lambda p, x, i=i: model.apply_layer(i, p, x) for i in range(len(layers))]
    return cls(plat, layers, layer_fns=fns, layer_args=list(zip(params, xs)), reps=2)


@pytest.mark.parametrize("evaluator_cls", [AnalyticEvaluator, DatabaseEvaluator, MeasuringEvaluator])
def test_stage_times_are_the_table_over_the_stage(evaluator_cls):
    """One stage-time model: each stage is its layers' per-index times over
    the stage EP's DVFS scale, plus the boundary's link."""
    layers = network_layers("synthnet")[:3]
    plat = paper_platform(2)
    pm = uniform_power(plat)
    ev = _evaluator(evaluator_cls, plat.with_power(pm), layers)
    conf = PipelineConfig(stages=(2, 1), eps=(0, 1))
    pm.set_level(0, 2)
    assert pm.scale(0) != 1.0
    link = ev.transfer_times(conf)
    want = []
    for s, (a, b) in enumerate(conf.boundaries()):
        ep = conf.eps[s]
        t = sum(ev.layer_time_by_index(i, ep) for i in range(a, b)) / pm.scale(ep)
        want.append(t + link[s] if s < conf.depth - 1 else t)
    assert ev.stage_times(conf) == want


def test_trace_accounts_cost_and_curve():
    layers, plat = _setup()
    tr = Trace(DatabaseEvaluator(plat, layers), setup_cost=5.0)
    conf = PipelineConfig(stages=(9, 9), eps=(0, 1))
    tr.execute(conf)
    assert tr.wall > 5.0  # setup + measurement cost
    curve = tr.convergence_curve()
    assert len(curve) == 1 and curve[0][1] == tr.best().throughput


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------


def test_es_beats_or_matches_everyone():
    layers, plat = _setup()
    ws = weights(layers)
    ev = DatabaseEvaluator(plat, layers)
    es = exhaustive_search(Trace(ev), 18, max_depth=2)
    for algo in (hill_climbing, simulated_annealing, random_walk):
        res = algo(Trace(ev), 18, budget_s=30.0, seed=1)
        assert res.best_throughput <= es.best_throughput * 1.001 or res.best_conf.depth > 2

    shisha = run_shisha(ws, Trace(ev), "H3", n_stages=2)
    assert shisha.result.best_throughput >= 0.85 * es.best_throughput


def test_pipe_search_runs_and_respects_budget():
    layers, plat = _setup()
    ws = weights(layers)
    tr = Trace(DatabaseEvaluator(plat, layers), setup_cost=2.0)
    res = pipe_search(tr, ws, budget_s=20.0, max_depth=3)
    assert res.best_throughput > 0
    assert tr.wall >= 2.0


def test_budgets_respected():
    layers, plat = _setup()
    ev = DatabaseEvaluator(plat, layers)
    tr = Trace(ev)
    random_walk(tr, 18, budget_s=3.0, seed=0)
    # at most ONE trial may start past the budget; bound its worst cost
    # (whole net on the slowest EP: fill + measure_batches beats + reconfig)
    worst_beat = sum(max(ev.layer_time_by_index(i, e) for e in range(plat.n_eps)) for i in range(18))
    assert tr.wall < 3.0 + (tr.measure_batches + 1) * worst_beat + tr.reconfig_overhead
