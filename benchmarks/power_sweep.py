"""Power sweep — cap-blind vs cap-aware DVFS tuning under a package cap.

    PYTHONPATH=src python -m benchmarks.power_sweep [--quick]

The acceptance scenario of the power/thermal subsystem, run on the
congested mesh cell of :mod:`benchmarks.fig9_interconnect` (2x4 mesh, a
co-tenant hammering the FEP-row links) with a :class:`~repro.power.PowerModel`
attached.  For each swept cap fraction two arms tune and then serve the
*same* seeded arrival stream:

  * **cap-blind** — the paper's loop (``run_shisha``), oblivious to the
    package budget: all EPs stay at nominal clocks, so under a binding cap
    its served peak package draw *violates* the budget.
  * **cap-aware** — a warm re-tune with ``tune(dvfs=True)``: per-EP
    frequency levels become tuned state, cap-infeasible candidates are
    rejected before being paid, and the adopted level vector satisfies the
    cap by construction.

Both arms serve with the thermal RC model live, so each reports
joules/request, peak/average package watts, throttle events and the
hottest chiplet temperature — the energy price of staying under the
budget, next to the throughput price.

The full payload lands in ``experiments/benchmarks/power_sweep.json`` and
the acceptance cell's headline (tightest swept cap) additionally in
``BENCH_power_sweep.json`` at the repo root; both are strict JSON (an
uncapped model reports ``cap_w`` as ``null``, never ``inf``).  Everything here is deterministic:
database oracle, seeded traffic, seeded thermal parameter jitter.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from repro.core import DatabaseEvaluator, Trace, paper_platform, weights
from repro.core.heuristics import run_shisha
from repro.core.tuner import tune
from repro.interconnect import Flow, mesh2d, uniform_fabric
from repro.models.cnn import network_layers
from repro.power import uniform_power, uniform_thermal
from repro.serve import PoissonTraffic, ServingSimulator

from .common import save

ROOT = Path(__file__).resolve().parent.parent

#: the fig9_interconnect congested cell: low-bandwidth 2x4 mesh with a
#: steady co-tenant on the links joining the FEP row
LINK_BW = 1e8
CONGESTOR_PAIRS = ((0, 1), (1, 2), (2, 3), (0, 3))
CONGESTOR_BYTES = 2e6

#: package cap as a fraction of the blind schedule's nominal all-busy
#: draw — every fraction below 1.0 is binding at nominal clocks
CAP_FRACTIONS = (0.9, 0.8, 0.7)
CAP_FRACTIONS_QUICK = (0.8,)

THERMAL_SEED = 11


def _cell():
    layers = network_layers("synthnet")
    plat = paper_platform(8).with_fabric(
        uniform_fabric(mesh2d(2, 4, bw=LINK_BW, latency=1e-6))
    )
    bg = tuple(
        Flow(src=s, dst=d, nbytes=CONGESTOR_BYTES, nodes=True)
        for s, d in CONGESTOR_PAIRS
    )
    return layers, plat, bg


def _powered_evaluator(plat, layers, bg, cap_w):
    """Fresh evaluator over a fresh powered platform (arms must not share
    the mutable level vector)."""
    pm = uniform_power(
        plat, cap_w=cap_w, thermal=uniform_thermal(plat.n_eps, seed=THERMAL_SEED)
    )
    ev = DatabaseEvaluator(plat.with_power(pm), layers)
    ev.background_flows = bg
    return ev, pm


def _serve(ev, conf, arrivals, horizon, slo) -> dict:
    sim = ServingSimulator(ev, conf, slo=slo)
    res = sim.run(arrivals, horizon)
    p = res.power
    return {
        "n_completed": res.n_completed,
        "p99_latency_s": res.p99,
        "energy_j": p["energy_j"],
        "joules_per_request": p["joules_per_request"],
        "peak_package_w": p["peak_package_w"],
        "avg_package_w": p["avg_package_w"],
        "cap_w": p["cap_w"],
        "throttle_events": p["throttle_events"],
        "max_temp_c": p["max_temp_c"],
        "dvfs_levels": p["dvfs_levels"],
    }


def sweep_cell(
    cap_fraction, blind, blind_serve, layers, plat, bg, arrivals, horizon, slo, verbose
) -> dict:
    """One binding cap: the blind arm's measured serve vs a cap-aware
    warm re-tune (DVFS knobs live, infeasible candidates rejected)."""
    cap_w = cap_fraction * blind_serve["peak_package_w"]

    aware_ev, aware_pm = _powered_evaluator(plat, layers, bg, cap_w=cap_w)
    aware_trace = Trace(aware_ev)
    aware = tune(blind.best_conf, aware_trace, dvfs=True)
    assert aware.dvfs_levels is not None
    assert aware_pm.cap_feasible(aware.best_conf.eps)

    cell = {
        "cap_fraction": cap_fraction,
        "cap_w": cap_w,
        "blind_throughput": blind.best_throughput,
        "aware_throughput": aware.best_throughput,
        "aware_retune_trials": aware_trace.n_trials,
        "aware_dvfs_levels": list(aware.dvfs_levels),
        # the blind serve is cap-independent physics (nominal clocks, no
        # enforcement); only its *reported* cap changes across the sweep
        "blind": dict(blind_serve, cap_w=cap_w),
        "aware": _serve(aware_ev, aware.best_conf, arrivals, horizon, slo),
    }
    cell["blind_violates_cap"] = cell["blind"]["peak_package_w"] > cap_w
    cell["aware_meets_cap"] = cell["aware"]["peak_package_w"] <= cap_w
    if verbose:
        print(
            f"  power_sweep cap={cap_fraction:.2f} ({cap_w:6.1f} W): "
            f"blind peak={cell['blind']['peak_package_w']:6.1f} W "
            f"({cell['blind']['joules_per_request']:.2f} J/req), "
            f"aware peak={cell['aware']['peak_package_w']:6.1f} W "
            f"({cell['aware']['joules_per_request']:.2f} J/req) -> "
            f"blind violates: {cell['blind_violates_cap']}, "
            f"aware meets: {cell['aware_meets_cap']}"
        )
    return cell


def run(verbose: bool = True, quick: bool = False) -> dict:
    horizon = 40.0 if quick else 120.0
    fractions = CAP_FRACTIONS_QUICK if quick else CAP_FRACTIONS

    layers, plat, bg = _cell()
    ws = weights(layers)
    # cap-blind arm, once: the paper's loop at nominal clocks, then served
    # uncapped — its measured peak draw is the self-calibrated reference
    # every swept cap binds against (a budget *below* observed draw)
    blind_ev, _ = _powered_evaluator(plat, layers, bg, cap_w=float("inf"))
    blind = run_shisha(ws, Trace(blind_ev), "H3").result
    rate = 0.45 * blind.best_throughput
    arrivals = PoissonTraffic(rate=rate, seed=29).arrivals(horizon)
    slo = 3.0 * sum(blind_ev.stage_times(blind.best_conf))
    blind_serve = _serve(blind_ev, blind.best_conf, arrivals, horizon, slo)

    cells = [
        sweep_cell(
            f, blind, blind_serve, layers, plat, bg, arrivals, horizon, slo, verbose
        )
        for f in fractions
    ]

    # acceptance at every binding cap: blind violates, aware satisfies, and
    # both arms priced their energy
    for cell in cells:
        assert cell["blind_violates_cap"], (
            f"cap {cell['cap_fraction']}: blind peak "
            f"{cell['blind']['peak_package_w']:.1f} W never exceeded the "
            f"{cell['cap_w']:.1f} W cap — the cap is not binding"
        )
        assert cell["aware_meets_cap"], (
            f"cap {cell['cap_fraction']}: aware peak "
            f"{cell['aware']['peak_package_w']:.1f} W breaks the cap"
        )
        assert cell["blind"]["joules_per_request"] is not None
        assert cell["aware"]["joules_per_request"] is not None

    tightest = min(cells, key=lambda c: c["cap_fraction"])
    payload = {
        "bench": "power_sweep",
        "cell": {"net": "synthnet", "topology": "mesh2x4", "congestor_flows": len(CONGESTOR_PAIRS)},
        "horizon_s": horizon,
        "offered_rate": rate,
        "sweep": cells,
        # headline scalars (tightest swept cap) for the BENCH_ artifacts
        "cap_fraction": tightest["cap_fraction"],
        "cap_w": tightest["cap_w"],
        "blind_peak_package_w": tightest["blind"]["peak_package_w"],
        "aware_peak_package_w": tightest["aware"]["peak_package_w"],
        "blind_joules_per_request": tightest["blind"]["joules_per_request"],
        "aware_joules_per_request": tightest["aware"]["joules_per_request"],
        "blind_violates_cap": tightest["blind_violates_cap"],
        "aware_meets_cap": tightest["aware_meets_cap"],
    }
    save("power_sweep", payload)
    out = ROOT / "BENCH_power_sweep.json"
    out.write_text(json.dumps(payload, indent=2) + "\n")
    if verbose:
        print(f"  power_sweep payload -> {out.name}")
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="single cap fraction, shorter serve")
    args = ap.parse_args()
    run(verbose=True, quick=args.quick)


if __name__ == "__main__":
    main()
