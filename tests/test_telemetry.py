"""Telemetry subsystem: exact metrics, deterministic traces, no-op pins.

Three contracts under test:

  1. **Exactness** — histogram quantiles are nearest-rank on the full
     observation multiset, bit-identical to the serving simulator's own
     ``percentile`` arithmetic.
  2. **Determinism** — two seeded co-serve runs export byte-identical JSONL
     and Chrome traces (simulated timestamps only, first-seen pid/tid
     mapping), and the exported Chrome trace is strict JSON carrying spans
     from all three layers (request lifecycle, re-tune window, fabric flow
     window).
  3. **Off-by-default** — passing ``NULL`` (or nothing) leaves every
     existing summary bit-for-bit unchanged and records nothing, and the
     instrumented event loop's no-op path still clears a conservative
     dispatch-rate floor.
"""

from __future__ import annotations

import dataclasses
import json
import math
import time

import pytest

from repro.core import DatabaseEvaluator, Trace, paper_platform, weights
from repro.core.heuristics import run_shisha
from repro.interconnect import Flow, mesh2d, uniform_fabric
from repro.models.cnn import network_layers
from repro.serve import (
    ContinuousShisha,
    PoissonTraffic,
    ReplayTraffic,
    ServingSimulator,
    Tenant,
    co_serve,
)
from repro.serve.simulator import EventLoop, percentile
from repro.telemetry import NULL, Histogram, NullTelemetry, Telemetry, live


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_quantiles_match_simulator_percentile():
    vals = [0.7, 0.1, 3.2, 0.1, 2.5, 1.9, 0.4, 5.0, 0.9, 2.2, 0.3]
    h = Histogram("t")
    for v in vals:
        h.observe(v)
    ref = sorted(vals)
    for q in (0.0, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert h.quantile(q) == percentile(ref, q)
    snap = h.snapshot()
    assert snap["count"] == len(vals)
    assert snap["min"] == min(vals) and snap["max"] == max(vals)
    assert snap["sum"] == pytest.approx(sum(vals))
    assert snap["p50"] == percentile(ref, 0.5)
    assert snap["p95"] == percentile(ref, 0.95)


def test_registry_get_or_create_and_kind_mismatch():
    tl = Telemetry()
    c = tl.counter("x")
    c.inc()
    c.inc(2.5)
    assert tl.counter("x") is c and c.value == 3.5
    tl.gauge("g").set(7)
    tl.histogram("h").observe(1.0)
    with pytest.raises(TypeError):
        tl.histogram("x")
    assert tl.registry.names() == ["g", "h", "x"]
    snap = tl.metrics_snapshot()
    assert snap["x"] == {"kind": "counter", "value": 3.5}
    assert snap["g"] == {"kind": "gauge", "value": 7}


def test_live_normalizes_null_and_none():
    tl = Telemetry()
    assert live(tl) is tl
    assert live(None) is None
    assert live(NULL) is None
    assert live(NullTelemetry()) is None


# ---------------------------------------------------------------------------
# no-op pins: NULL changes nothing, records nothing
# ---------------------------------------------------------------------------


def _drift_sim(telemetry):
    layers = network_layers("synthnet")
    plat = paper_platform(8)
    ev = DatabaseEvaluator(plat, layers)
    sh = run_shisha(weights(layers), Trace(ev), "H3")
    conf, cap = sh.result.best_conf, sh.result.best_throughput
    tuner = ContinuousShisha(
        plat,
        layers,
        make_evaluator=lambda p: DatabaseEvaluator(p, layers),
        measure_batches=2,
        alpha=4,
    )
    sim = ServingSimulator(ev, conf, slo=3.0, autotuner=tuner, telemetry=telemetry)
    times = ev.stage_times(conf)
    bad_ep = conf.eps[max(range(conf.depth), key=times.__getitem__)]
    sim.schedule_slowdown(10.0, bad_ep, 3.0)
    traffic = PoissonTraffic(rate=0.5 * cap, seed=3)
    return sim.run(traffic.arrivals(40.0), 40.0)


def test_nullsink_serve_summary_bit_identical():
    base = _drift_sim(None)
    null = _drift_sim(NULL)
    assert base.reconfigs, "scenario must actually re-tune to pin anything"
    assert dataclasses.asdict(null) == dataclasses.asdict(base)
    # the shared NULL sink recorded nothing anywhere
    assert len(NULL.registry) == 0 and len(NULL.tracer) == 0


def test_nullsink_fabric_adaptive_pricing_identical():
    topo = mesh2d(3, 3)
    flows = [Flow(0, 8, 4e6, nodes=True), Flow(2, 6, 4e6, nodes=True), Flow(0, 6, 2e6, nodes=True)]
    bare = uniform_fabric(topo, mc_bw=None, routing="adaptive", seed=1)
    live_tl = Telemetry()
    for sink, expect_recording in ((None, False), (NULL, False), (live_tl, True)):
        fab = uniform_fabric(topo, mc_bw=None, routing="adaptive", seed=1)
        fab.telemetry = live(sink)
        assert fab.flow_times(flows) == bare.flow_times(flows)
        recorded = "fabric.routing_passes" in (
            sink.registry if sink is not None else Telemetry().registry
        )
        assert recorded == expect_recording
    snap = live_tl.metrics_snapshot()
    assert snap["fabric.routing_passes"]["value"] == 1.0
    assert "fabric.adaptive_delta_s" in snap
    assert snap["fabric.contention_factor"]["max"] >= 1.0


def test_trace_telemetry_records_trials_without_changing_wall():
    layers = network_layers("alexnet")
    plat = paper_platform(4)
    bare = Trace(DatabaseEvaluator(plat, layers))
    tl = Telemetry()
    instrumented = Trace(DatabaseEvaluator(plat, layers), telemetry=tl)
    r1 = run_shisha(weights(layers), bare, "H3")
    r2 = run_shisha(weights(layers), instrumented, "H3")
    assert r2.result.best_conf == r1.result.best_conf
    assert instrumented.wall == bare.wall
    snap = tl.metrics_snapshot()
    assert snap["tune.trials"]["value"] == bare.n_trials
    assert snap["tune.trial_cost_s"]["count"] == bare.n_trials


def test_event_loop_noop_dispatch_floor():
    class Owner:
        def _dispatch(self, t, kind, payload):
            pass

    owner = Owner()
    loop = EventLoop()
    n = 50_000
    for i in range(n):
        loop.push(i * 1e-6, 0, owner, None)
    t0 = time.perf_counter()
    loop.run(math.inf)
    wall = time.perf_counter() - t0
    assert loop.n_dispatched == n
    assert loop.telemetry is None
    # conservative floor: the un-instrumented loop must stay a hot path
    assert n / wall > 20_000, f"event loop at {n / wall:.0f} ev/s"


def test_serve_telemetry_overhead_floor():
    """Instrumented serving must stay close to the bare run.

    Arms are warmed once and then *interleaved* best-of, so machine-load
    drift cancels out of the ratio instead of biasing it.  The bound is deliberately loose — the
    optimized hot path (direct ``TraceEvent`` appends from the simulator)
    measures ~1.4-1.65x on the PR 9 drain engine (the faster bare loop
    shrank the denominator; it was ~1.4x on the legacy heap engine),
    while a regression to the pre-optimization path (two delegation
    layers per span) would now sit well above 2x — so the floor still
    catches the old path without flaking on a loaded machine.
    """
    layers = network_layers("synthnet")
    plat = paper_platform(8)
    ev = DatabaseEvaluator(plat, layers)
    sh = run_shisha(weights(layers), Trace(ev), "H3")
    conf, cap = sh.result.best_conf, sh.result.best_throughput
    horizon = 120.0
    arrivals = PoissonTraffic(rate=0.6 * cap, seed=7).arrivals(horizon)

    def arm(instrumented: bool) -> float:
        tl = Telemetry() if instrumented else None
        sim = ServingSimulator(ev, conf, slo=3.0, telemetry=tl)
        t0 = time.perf_counter()
        sim.run(arrivals, horizon)
        return time.perf_counter() - t0

    arm(False), arm(True)  # warmup, untimed
    bare = tel = math.inf
    for _ in range(5):
        bare = min(bare, arm(False))
        tel = min(tel, arm(True))
    ratio = tel / bare
    assert ratio < 1.9, f"telemetry serve overhead {ratio:.2f}x (bare {bare:.3f}s)"


# ---------------------------------------------------------------------------
# co-serve: determinism + three-layer trace acceptance
# ---------------------------------------------------------------------------


def _co_serve_run(telemetry):
    plat = paper_platform(8).with_fabric(uniform_fabric(mesh2d(2, 4)))
    horizon = 8.0
    tenants = [
        Tenant(
            name="resnet50",
            layers=tuple(network_layers("resnet50")),
            traffic=ReplayTraffic.record(PoissonTraffic(rate=30, seed=1), horizon),
            slo=1.0,
        ),
        Tenant(
            name="alexnet",
            layers=tuple(network_layers("alexnet")),
            traffic=ReplayTraffic.record(PoissonTraffic(rate=40, seed=2), horizon),
            slo=0.5,
        ),
    ]
    return co_serve(
        plat,
        tenants,
        horizon=horizon,
        measure_batches=2,
        alpha=4,
        faults=[("dropout", 2.0, 0)],
        telemetry=telemetry,
    )


def test_seeded_co_serve_exports_are_byte_identical():
    tl_a, tl_b = Telemetry(), Telemetry()
    res_a = _co_serve_run(tl_a)
    res_b = _co_serve_run(tl_b)
    assert res_a.aggregate_slo_rate == res_b.aggregate_slo_rate
    jsonl_a, jsonl_b = tl_a.export_jsonl(), tl_b.export_jsonl()
    assert jsonl_a and jsonl_a == jsonl_b
    chrome_a = json.dumps(tl_a.export_chrome_trace(), sort_keys=True)
    chrome_b = json.dumps(tl_b.export_chrome_trace(), sort_keys=True)
    assert chrome_a == chrome_b
    assert json.dumps(tl_a.metrics_snapshot(), sort_keys=True) == json.dumps(
        tl_b.metrics_snapshot(), sort_keys=True
    )


def test_chrome_trace_has_all_three_layers_and_tenant_processes():
    tl = Telemetry()
    res = _co_serve_run(tl)
    trace = tl.export_chrome_trace()
    # strict JSON (Perfetto rejects NaN/Infinity)
    text = json.dumps(trace, allow_nan=False)
    assert json.loads(text)["traceEvents"]
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    cats = {e.get("cat") for e in spans}
    assert {"request", "retune", "fabric"} <= cats, f"missing layers in {cats}"
    for e in spans:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["dur"] >= 0 and e["ts"] >= 0
    procs = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert {"resnet50", "alexnet"} <= procs  # tenants render as processes
    tracks = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e["name"] == "thread_name"
    }
    assert any(t.startswith("ep") for t in tracks)  # EPs render as tracks
    assert "flows" in tracks  # fabric flow windows render as a track
    # the repartition decision shows up as a coserve instant
    assert res.repartitions
    assert any(
        e.get("ph") == "i" and e["name"] == "repartition" for e in events
    )
    # JSONL round-trips line by line
    lines = tl.export_jsonl().splitlines()
    assert len(lines) == len(tl.tracer.events)
    for line in lines:
        json.loads(line)


def test_nullsink_co_serve_matches_bare_run():
    bare = _co_serve_run(None)
    null = _co_serve_run(NULL)
    assert [dataclasses.asdict(r.sim) for r in null.results] == [
        dataclasses.asdict(r.sim) for r in bare.results
    ]
    assert [dataclasses.asdict(e) for e in null.repartitions] == [
        dataclasses.asdict(e) for e in bare.repartitions
    ]
    assert null.partitions == bare.partitions


# ---------------------------------------------------------------------------
# package-deal steals
# ---------------------------------------------------------------------------


def test_extreme_pressure_victim_steals_a_bundle():
    plat = paper_platform(8)
    horizon = 12.0
    layers_v = tuple(network_layers("synthnet"))
    layers_d = tuple(network_layers("alexnet"))
    # victim demand ~3x what its launch partition can serve; donor idle
    from repro.serve import partition_eps, subplatform

    parts = partition_eps(plat, 2, "interleaved")
    cap = run_shisha(
        weights(list(layers_v)),
        Trace(DatabaseEvaluator(subplatform(plat, parts[0], "v"), list(layers_v))),
        "H3",
    ).result.best_throughput
    tenants = [
        Tenant(
            name="victim",
            layers=layers_v,
            traffic=ReplayTraffic.record(
                PoissonTraffic(rate=3.0 * cap, seed=5), horizon
            ),
            slo=1.0,
        ),
        Tenant(
            name="donor",
            layers=layers_d,
            traffic=ReplayTraffic.record(PoissonTraffic(rate=0.5, seed=6), horizon),
            slo=5.0,
        ),
    ]
    dead = parts[0][0]
    tl = Telemetry()
    res = co_serve(
        plat,
        tenants,
        horizon=horizon,
        measure_batches=2,
        alpha=4,
        faults=[("dropout", 3.0, dead)],
        telemetry=tl,
        max_bundle=3,
    )
    ev = next(e for e in res.repartitions if e.kind == "dropout")
    assert ev.victim == "victim"
    assert len(ev.bundle) >= 2, f"expected a package deal, got {ev.bundle}"
    # first deal mirrors the legacy single-steal fields
    assert ev.bundle[0]["donor"] == ev.donor
    assert ev.bundle[0]["ep"] == ev.stolen_ep
    assert ev.bundle[0]["price"] == ev.price
    for deal in ev.bundle:
        assert deal["donor"] == "donor"
        assert set(deal) == {
            "donor",
            "ep",
            "price",
            "gain",
            "surplus",
            "victim_at_risk_after",
        }
        assert deal["surplus"] is None or deal["surplus"] > 0
    # every stolen EP actually moved victim-ward, partitions stay disjoint
    stolen = [d["ep"] for d in ev.bundle]
    assert set(stolen) <= set(ev.partitions["victim"])
    assert not set(ev.partitions["victim"]) & set(ev.partitions["donor"])
    # strict JSON payload (inf gains serialized as None)
    json.dumps([dict(d) for d in ev.bundle], allow_nan=False)
    # and the event is on the telemetry timeline with its pricing breakdown
    inst = next(
        e
        for e in tl.tracer.events
        if e.name == "repartition" and e.dur is None
    )
    assert len(inst.args["bundle"]) == len(ev.bundle)
    assert tl.metrics_snapshot()["coserve.eps_stolen"]["value"] == len(ev.bundle)


def test_single_bundle_is_legacy_single_steal():
    """max_bundle=1 must reproduce the pre-bundle rebalance exactly."""
    from repro.serve.multitenant import ElasticPartitioner

    plat = paper_platform(8)
    layers = {
        "a": tuple(network_layers("synthnet")),
        "b": tuple(network_layers("alexnet")),
    }
    tenants = {
        name: Tenant(
            name=name, layers=ls, traffic=PoissonTraffic(rate=1, seed=1), slo=1.0
        )
        for name, ls in layers.items()
    }
    pricer = ElasticPartitioner(
        plat, lambda p, L: DatabaseEvaluator(p, L), "H3"
    )
    partitions = {"a": (0, 2, 4), "b": (1, 3, 5, 6, 7)}
    loads = {"a": (50.0, 20.0), "b": (0.1, 0.0)}
    single = pricer.rebalance(partitions, "a", tenants, loads)
    deals, parts = pricer.rebalance_bundle(
        partitions, "a", tenants, loads, max_bundle=1
    )
    assert single is not None and len(deals) == 1
    donor, ep, price = single
    assert (deals[0]["donor"], deals[0]["ep"], deals[0]["price"]) == (donor, ep, price)
    assert parts["a"] == partitions["a"] + (ep,)
    # input mapping was not mutated
    assert partitions == {"a": (0, 2, 4), "b": (1, 3, 5, 6, 7)}
