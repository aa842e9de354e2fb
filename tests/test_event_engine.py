"""Event-engine contracts: drain-sorted :class:`EventLoop` vs legacy heap.

PR 9 replaced the per-event ``heapq`` loop with a drain-sorted engine
(sort staged events once per refill, interleave a small near-heap for
mid-dispatch pushes).  The refactor is only admissible because the
dispatch contract is *bit-for-bit* preserved; this module is that pin:

  * **windowed-run regression** — ``run(h)`` must peek, not pop, at the
    horizon: an event past ``h`` stays queued for the next window.  The
    legacy loop silently consumed it (the beyond-horizon loss bug this PR
    fixes); both engines are now held to peek semantics.
  * **order property** — dispatch order equals ``sorted`` by
    ``(time, kind, push-order)``, including heavy timestamp ties.
  * **cross-engine equivalence** — randomized trials with mid-dispatch
    follow-up pushes (including same-time, lower-kind pushes that must
    pre-empt the current drain) dispatch identically on both engines.
  * **end-to-end pins** — seeded serve scenarios (plain, scripted faults,
    power+thermal, adaptive fabric) and an elastic faulted co-serve run
    produce field-for-field identical results under either engine.
  * **throughput floor** — the drain engine must hold >= 2.5x the legacy
    heap on raw no-op dispatch (relative, in-process, so CI machine speed
    cancels).
"""

import math
import random
import time

import pytest

from repro.core import DatabaseEvaluator, Trace, paper_platform, weights
from repro.core.heuristics import run_shisha
from repro.models.cnn import network_layers
from repro.serve import (
    PoissonTraffic,
    ReplayTraffic,
    ServingSimulator,
    Tenant,
    co_serve,
)
from repro.serve.simulator import EventLoop, HeapEventLoop
from repro.serve.traffic import _rng


ENGINES = [EventLoop, HeapEventLoop]
ENGINE_IDS = ["drain", "heap"]


class _Recorder:
    """Owner that records every dispatch it receives."""

    def __init__(self):
        self.events = []

    def _dispatch(self, t, kind, payload):
        self.events.append((t, kind, payload))


class _Chainer(_Recorder):
    """Owner whose dispatches deterministically push follow-up events.

    The follow-up schedule (seeded, identical across engines) exercises the
    hard cases: pushes *into* the active drain region, zero-delta pushes,
    and same-time lower-kind pushes that must still dispatch before later
    drain entries.
    """

    def __init__(self, loop, seed):
        super().__init__()
        self.loop = loop
        self.rng = random.Random(seed)

    def _dispatch(self, t, kind, payload):
        super()._dispatch(t, kind, payload)
        r = self.rng.random()
        if r < 0.3 and payload < 4:
            dt = self.rng.choice([0.0, 1e-9, 0.001, 0.01, 0.5, 10.0])
            self.loop.push(t + dt, self.rng.randrange(5), self, payload + 1)
        if r < 0.05:
            self.loop.push(t, 0, self, payload + 1)  # same time, lowest kind


def _scripted_run(cls, seed, horizons):
    """Seeded random pushes + chained follow-ups, run over ``horizons``."""
    rng = random.Random(seed)
    loop = cls()
    rec = _Chainer(loop, seed * 7 + 1)
    for _ in range(rng.randrange(1, 200)):
        # mix of continuous times and small integers (deliberate ties)
        t = rng.choice([rng.uniform(0, 100), float(rng.randrange(10))])
        loop.push(t, rng.randrange(5), rec, 0)
    for h in horizons:
        loop.run(h)
    return rec.events, loop.n_dispatched


# ---------------------------------------------------------------------------
# windowed runs: peek-don't-pop at the horizon
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cls", ENGINES, ids=ENGINE_IDS)
def test_beyond_horizon_event_is_not_consumed(cls):
    """The PR 9 bug: the legacy loop popped the first beyond-horizon event
    before noticing it was late, silently dropping it from later windows."""
    rec = _Recorder()
    loop = cls()
    loop.push(5.0, 0, rec, "late")
    loop.push(0.5, 0, rec, "early")
    loop.run(1.0)
    assert rec.events == [(0.5, 0, "early")]
    assert len(loop) == 1  # the late event is still queued, not lost
    loop.run(10.0)
    assert rec.events == [(0.5, 0, "early"), (5.0, 0, "late")]
    assert len(loop) == 0


@pytest.mark.parametrize("cls", ENGINES, ids=ENGINE_IDS)
def test_windowed_run_equals_single_horizon(cls):
    """Running in 3 windows dispatches exactly what one run would."""
    for seed in range(40):
        single = _scripted_run(cls, seed, [120.0])
        windowed = _scripted_run(cls, seed, [15.0, 40.0, 120.0])
        assert single == windowed, f"seed {seed}: windowed != single-horizon"


def test_repeated_and_zero_width_windows_are_idempotent():
    rec = _Recorder()
    loop = EventLoop()
    for t in (3.0, 1.0, 2.0):
        loop.push(t, 0, rec, t)
    loop.run(1.0)
    loop.run(1.0)  # re-running an exhausted window dispatches nothing new
    loop.run(0.0)
    assert rec.events == [(1.0, 0, 1.0)]
    loop.run(math.inf)
    assert [p for _, _, p in rec.events] == [1.0, 2.0, 3.0]


# ---------------------------------------------------------------------------
# dispatch-order property
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cls", ENGINES, ids=ENGINE_IDS)
def test_dispatch_order_is_sorted_time_kind_pushorder(cls):
    """Property: dispatch order == sorted (t, kind, seq), under heavy ties.

    Payloads are the 1-based push index, which equals the engine's internal
    ``seq``, so the recorded stream directly witnesses the tiebreak chain:
    time first, kind next, push order last.
    """
    for trial in range(30):
        rng = random.Random(1000 + trial)
        loop = cls()
        rec = _Recorder()
        pushed = []
        for s in range(1, rng.randrange(2, 150)):
            t = float(rng.randrange(5))  # 5 distinct times -> many ties
            k = rng.randrange(3)
            loop.push(t, k, rec, s)
            pushed.append((t, k, s))
        loop.run(math.inf)
        assert rec.events == sorted(pushed), f"trial {trial}: order violated"
        assert loop.n_dispatched == len(pushed)


@pytest.mark.parametrize("windows", [None, (15.0, 15.0, 40.0, 99.0, 120.0)])
def test_engines_dispatch_identically(windows):
    """Randomized cross-engine equivalence, with mid-dispatch pushes."""
    horizons = list(windows) if windows else [120.0]
    for seed in range(60):
        a = _scripted_run(EventLoop, seed, horizons)
        b = _scripted_run(HeapEventLoop, seed, horizons)
        assert a == b, f"seed {seed}: engines diverged"


# ---------------------------------------------------------------------------
# push_batch: bulk priming == N sequential pushes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cls", ENGINES, ids=ENGINE_IDS)
def test_push_batch_equals_sequential_pushes(cls):
    rng = random.Random(77)
    batch = sorted(rng.uniform(0, 50) for _ in range(200))
    singles = [(rng.uniform(0, 50), rng.randrange(5)) for _ in range(40)]

    def prime(bulk):
        loop = cls()
        rec = _Recorder()
        for t, k in singles[:20]:
            loop.push(t, k, rec, "pre")
        if bulk:
            loop.push_batch(batch, 1, rec, list(range(len(batch))))
        else:
            for i, t in enumerate(batch):
                loop.push(t, 1, rec, i)
        for t, k in singles[20:]:
            loop.push(t, k, rec, "post")
        loop.run(math.inf)
        return rec.events, loop.n_dispatched

    assert prime(bulk=True) == prime(bulk=False)


def test_push_batch_mid_drain_interleaves_correctly():
    """A batch pushed *during* dispatch (drain active) must land exactly
    where sequential pushes would — including entries below the drain tail."""

    class _BatchOnFirst(_Recorder):
        def __init__(self, loop, bulk):
            super().__init__()
            self.loop, self.bulk, self.fired = loop, bulk, False

        def _dispatch(self, t, kind, payload):
            super()._dispatch(t, kind, payload)
            if not self.fired:
                self.fired = True
                times = [t + 0.1, t + 0.2, 90.0]
                if self.bulk:
                    self.loop.push_batch(times, 0, self, ["a", "b", "c"])
                else:
                    for ti, p in zip(times, ["a", "b", "c"]):
                        self.loop.push(ti, 0, self, p)

    outcomes = []
    for bulk in (True, False):
        loop = EventLoop()
        rec = _BatchOnFirst(loop, bulk)
        for t in (1.0, 2.0, 3.0, 50.0):
            loop.push(t, 1, rec, t)
        loop.run(math.inf)
        outcomes.append(rec.events)
    assert outcomes[0] == outcomes[1]
    assert [p for _, _, p in outcomes[0]] == [1.0, "a", "b", 2.0, 3.0, 50.0, "c"]


# ---------------------------------------------------------------------------
# vectorized Poisson arrivals: bit-exact vs the scalar reference loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rate,seed,horizon",
    [(0.5, 0, 60.0), (5.0, 3, 60.0), (120.0, 7, 40.0), (5000.0, 1, 2.0)],
)
def test_poisson_vectorized_matches_scalar_reference(rate, seed, horizon):
    """The chunked carry-in-cumsum draw must reproduce the scalar
    ``t += rng.exponential(...)`` loop bit-for-bit (the 5000-rate case
    crosses several chunk boundaries, where naive ``t + cumsum`` drifts)."""
    rng = _rng(seed)
    ref, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= horizon:
            break
        ref.append(t)
    assert PoissonTraffic(rate=rate, seed=seed).arrivals(horizon) == ref


# ---------------------------------------------------------------------------
# end-to-end: seeded serve results bit-for-bit across engines
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tuned():
    layers = network_layers("synthnet")
    plat = paper_platform(8)
    ev = DatabaseEvaluator(plat, layers)
    sh = run_shisha(weights(layers), Trace(ev), "H3")
    conf = sh.result.best_conf
    return {
        "layers": layers,
        "plat": plat,
        "conf": conf,
        "cap": sh.result.best_throughput,
        "slo": 3.0 * sum(ev.stage_times(conf)),
    }


def _serve_result(tuned, loop_cls, scenario):
    plat = tuned["plat"]
    if scenario == "power":
        from repro.power import uniform_power, uniform_thermal

        plat = plat.with_power(
            uniform_power(plat, thermal=uniform_thermal(plat.n_eps, seed=3))
        )
    elif scenario == "fabric":
        from repro.interconnect import mesh2d, uniform_fabric

        plat = plat.with_fabric(
            uniform_fabric(mesh2d(2, 4, bw=1e8, latency=1e-6), routing="adaptive")
        )
    ev = DatabaseEvaluator(plat, tuned["layers"])
    sim = ServingSimulator(ev, tuned["conf"], slo=tuned["slo"], loop=loop_cls())
    if scenario == "faults":
        sim.schedule_slowdown(8.0, 1, 2.0)
        sim.schedule_dropout(15.0, 0)
    arrivals = PoissonTraffic(rate=0.6 * tuned["cap"], seed=5).arrivals(30.0)
    return sim.run(arrivals, 30.0)


@pytest.mark.parametrize("scenario", ["plain", "faults", "power", "fabric"])
def test_sim_result_bit_for_bit_across_engines(tuned, scenario):
    res_new = _serve_result(tuned, EventLoop, scenario)
    res_old = _serve_result(tuned, HeapEventLoop, scenario)
    assert res_new == res_old  # every SimResult field, incl. power block


@pytest.mark.parametrize("order", ["drop-then-revive", "revive-then-drop"])
def test_same_timestamp_dropout_revival_dispatch_in_push_order(tuned, order):
    """Scripted dropout + revival of one EP at the *same* timestamp are
    both ``_PLATFORM`` events: the (time, kind, push-order) contract says
    the one pushed first wins, identically on both engines."""
    ep = tuned["conf"].eps[0]

    def run(loop_cls):
        ev = DatabaseEvaluator(tuned["plat"], tuned["layers"])
        sim = ServingSimulator(ev, tuned["conf"], slo=tuned["slo"], loop=loop_cls())
        sim.schedule_dropout(5.0, ep)
        if order == "drop-then-revive":
            sim.schedule_dropout(12.0, ep)
            sim.schedule_revival(12.0, ep)  # pushed last: EP ends alive
        else:
            sim.schedule_revival(12.0, ep)
            sim.schedule_dropout(12.0, ep)  # pushed last: EP stays dead
        arrivals = PoissonTraffic(rate=0.6 * tuned["cap"], seed=5).arrivals(30.0)
        return sim, sim.run(arrivals, 30.0)

    sim_new, res_new = run(EventLoop)
    sim_old, res_old = run(HeapEventLoop)
    assert res_new == res_old
    if order == "drop-then-revive":
        assert ep not in sim_new.dead and ep not in sim_old.dead
    else:
        assert ep in sim_new.dead and ep in sim_old.dead


def test_push_order_of_same_timestamp_faults_changes_the_outcome(tuned):
    """The two orders above are genuinely different programs — if they
    converged, the tie-break test would be vacuous."""
    ep = tuned["conf"].eps[0]

    def run(first, second):
        ev = DatabaseEvaluator(tuned["plat"], tuned["layers"])
        sim = ServingSimulator(ev, tuned["conf"], slo=tuned["slo"], loop=EventLoop())
        sim.schedule_dropout(5.0, ep)
        first(sim)
        second(sim)
        arrivals = PoissonTraffic(rate=0.6 * tuned["cap"], seed=5).arrivals(30.0)
        return sim.run(arrivals, 30.0)

    drop = lambda sim: sim.schedule_dropout(12.0, ep)
    revive = lambda sim: sim.schedule_revival(12.0, ep)
    assert run(drop, revive) != run(revive, drop)


def test_co_serve_result_bit_for_bit_across_engines():
    """Elastic, faulted shared-clock co-simulation under either engine."""
    plat = paper_platform(8)
    tenants = [
        Tenant(
            name="synthnet",
            layers=tuple(network_layers("synthnet")),
            traffic=ReplayTraffic.record(PoissonTraffic(rate=3.0, seed=11), 40.0),
            slo=2.7,
        ),
        Tenant(
            name="alexnet",
            layers=tuple(network_layers("alexnet")),
            traffic=ReplayTraffic.record(PoissonTraffic(rate=2.0, seed=12), 40.0),
            slo=2.0,
        ),
    ]

    def arm(loop_cls):
        return co_serve(
            plat,
            tenants,
            horizon=40.0,
            elastic=True,
            measure_batches=2,
            alpha=4,
            faults=[("dropout", 12.0, 0), ("slowdown", 20.0, 2, 3.0)],
            loop=loop_cls(),
        )

    res_new, res_old = arm(EventLoop), arm(HeapEventLoop)
    assert res_new == res_old  # results, repartitions, partitions, dead


# ---------------------------------------------------------------------------
# throughput: relative floor + committed-artifact witness
# ---------------------------------------------------------------------------


class _NullOwner:
    def _dispatch(self, t, kind, payload):
        pass


def test_drain_engine_dispatch_floor_vs_legacy():
    """Raw no-op dispatch: drain engine >= 2.5x the legacy heap.

    Relative and in-process (warmed, interleaved best-of), so absolute
    machine speed and load cancel; the measured ratio is ~4-6x, 2.5x
    leaves margin for CI jitter.
    """
    n = 100_000
    owner = _NullOwner()
    times = [i * 1e-6 for i in range(n)]
    payloads = [None] * n

    def arm(cls):
        loop = cls()
        loop.push_batch(times, 0, owner, payloads)
        t0 = time.perf_counter()
        loop.run(math.inf)
        wall = time.perf_counter() - t0
        assert loop.n_dispatched == n
        return wall

    arm(EventLoop), arm(HeapEventLoop)  # warmup, untimed
    new = old = math.inf
    for _ in range(5):
        new = min(new, arm(EventLoop))
        old = min(old, arm(HeapEventLoop))
    assert old / new >= 2.5, f"drain engine only {old / new:.2f}x the legacy heap"
