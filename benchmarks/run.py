"""Benchmark orchestrator — one harness per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--bench-json DIR]

Prints ``name,us_per_call,derived`` CSV rows per harness plus per-figure
summaries; raw payloads land in experiments/benchmarks/*.json.  With
``--bench-json DIR`` each executed harness additionally drops a
``BENCH_<name>.json`` artifact into DIR: its headline scalars (the
top-level numbers a trajectory plot wants) plus the harness wall time —
the machine-readable form CI uploads.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def headline(payload) -> dict:
    """The top-level scalars of a harness payload (trajectory material)."""
    if not isinstance(payload, dict):
        return {}
    return {
        k: v
        for k, v in payload.items()
        if isinstance(v, (bool, int, float)) or (isinstance(v, str) and len(v) <= 64)
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip the slowest figures")
    ap.add_argument("--only", default=None, help="comma-separated figure list")
    ap.add_argument(
        "--bench-json",
        default=None,
        metavar="DIR",
        help="also write BENCH_<figure>.json headline artifacts into DIR",
    )
    args = ap.parse_args()

    from . import fig4_convergence, fig5_quality, fig6_seed, fig7_heuristics, fig9_latency
    from . import chaos_sweep, fig9_interconnect, power_sweep
    from . import serve_sim

    figures = {
        "fig4": fig4_convergence.run,
        "fig5": fig5_quality.run,
        "fig6": fig6_seed.run,
        "fig7": fig7_heuristics.run,
        "fig9": fig9_latency.run,
        "fig9_interconnect": lambda: fig9_interconnect.run(quick=args.quick),
        "fig9_adaptive": lambda: fig9_interconnect.run_adaptive(quick=args.quick),
        "serve_sim": lambda: serve_sim.run(quick=args.quick),
        "multitenant_drift": lambda: serve_sim.run_multitenant_drift(quick=args.quick),
        "power_sweep": lambda: power_sweep.run(quick=args.quick),
        "chaos_sweep": lambda: chaos_sweep.run(quick=args.quick),
    }
    if args.only:
        keep = set(args.only.split(","))
        figures = {k: v for k, v in figures.items() if k in keep}
    if args.quick:
        figures.pop("fig6", None)

    bench_dir = Path(args.bench_json) if args.bench_json else None
    if bench_dir is not None:
        bench_dir.mkdir(parents=True, exist_ok=True)

    rows = []
    for name, fn in figures.items():
        t0 = time.perf_counter()
        print(f"[bench] {name} ...", flush=True)
        try:
            payload = fn()
            dt = (time.perf_counter() - t0) * 1e6
            rows.append(f"{name},{dt:.0f},ok")
            if bench_dir is not None:
                artifact = {
                    "figure": name,
                    "wall_us": dt,
                    "headline": headline(payload),
                }
                (bench_dir / f"BENCH_{name}.json").write_text(
                    json.dumps(artifact, indent=2) + "\n"
                )
        except Exception as e:  # keep the harness going; report at the end
            dt = (time.perf_counter() - t0) * 1e6
            rows.append(f"{name},{dt:.0f},FAILED:{type(e).__name__}:{e}")
            import traceback

            traceback.print_exc()
    print("\nname,us_per_call,derived")
    for r in rows:
        print(r)
    if any("FAILED" in r for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    main()
