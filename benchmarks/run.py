"""Benchmark harness — one entry per paper table/figure + the roofline.

  table1     FedAvg vs heterogeneity           (paper Table 1)
  table3     framework comparison + ablations  (paper Table 3)
  round_exec fused round executor vs the retired per-group loops
             (static + IFCA/FeSEM dynamic assignment, m=5/K=50)
  round_block scan-fused B=16 round blocks (donated carry, one metrics
             fetch per block) vs the per-round dispatch path, appended
             to BENCH_round_exec.json
  mesh2d     2-D (data, model) mesh vs the 1-D data mesh round time
             (m=5/K=50, 4 forced host devices, appended to
             BENCH_round_exec.json)
  population streamed ClientStore cohorts vs the pinned stacks +
             double-buffered prefetch overlap (N=10^4-10^5 virtual clients)
  robustness fault-tolerant runtime: checkpoint overhead + cold recovery,
             quarantine efficacy under injected NaN payloads, straggler
             deadline saving (BENCH_robustness.json)
  async      staleness-aware async runtime: async-vs-sync throughput
             under a straggler trace + the D=1 equivalence mode's
             overhead (BENCH_async.json)
  shift      distribution-shift migration: FedGroup static vs
             shift-detector migration vs IFCA under a scripted label
             swap (BENCH_shift.json)
  obs        telemetry layer: enabled-vs-disabled overhead on the fused
             round + schema self-lint of the bench's own telemetry dir
             via launch/inspect.py --check (BENCH_obs.json)
  fleet      coordinator/worker control plane: fleet-of-1 routed-lease
             overhead vs engine.run() + hard-killed-worker recovery
             latency (BENCH_fleet.json)
  docs       docs freshness: module doctests + README/docs path existence
  fig5       EDC vs MADC linearity             (paper Fig. 5)
  cost       clustering-measure cost           (paper §3.3 complexity claim)
  roofline   per-(arch×shape) roofline terms   (deliverable g)

``python -m benchmarks.run``          — full run
``python -m benchmarks.run --quick``  — reduced scales (CI-sized)
``python -m benchmarks.run --only table3,fig5``
``python -m benchmarks.run --json out.json``  — machine-readable results

Exit status is nonzero when a bench fails OR when a bench reports a perf
regression >2x against its committed BENCH_*.json baseline (cost watches
the MADC dispatch's relative speed; round_exec the static/IFCA/FeSEM
executor speedups; round_block the blocked-vs-per-round speedup; mesh2d
the 2-D/1-D round-time ratio; population the streamed-vs-pinned
round-time ratio and the prefetch-overlap speedup; robustness the
checkpoint overhead, quarantine efficacy and deadline saving; async the
async-vs-sync throughput and the D=1 equivalence-mode overhead; obs the
enabled-vs-disabled telemetry overhead on the fused round; shift the
migration-vs-static post-swap accuracy ratio; fleet the fleet-of-1
coordinator overhead) — docs/benchmarks.md documents the BENCH_*.json
schema and the gate semantics. Gate failures print a per-entry diff —
which bench, crash vs watched-metric regression, best recorded ->
measured — before the nonzero exit. ``--quick`` always includes the
round_exec, round_block, mesh2d, population, robustness, shift, fleet
and docs suites, even under ``--only``:

``python -m benchmarks.run --quick --only cost,table3``  — the CI perf gate
(effectively cost,table3,round_exec,round_block,mesh2d,population,
robustness,async,obs,shift,fleet,docs)

The harness installs a process-default telemetry (``repro.obs``), so the
``--json`` report carries per-bench per-stage span attribution under each
entry's ``"stages"`` key — the run inspector's breakdown, per bench.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import (async_bench, clustering_cost, docs_check,
                        eta_g_sweep, fig5_edc_madc, fleet_bench, mesh2d,
                        obs_bench, population_bench, robustness_bench,
                        roofline, round_block, shift_bench,
                        table1_heterogeneity, table3_frameworks)
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import telemetry as obs_telemetry

BENCHES = {
    "table1": table1_heterogeneity.main,
    "table3": table3_frameworks.main,
    "round_exec": table3_frameworks.round_executor_bench,
    "round_block": round_block.main,
    "mesh2d": mesh2d.main,
    "population": population_bench.main,
    "robustness": robustness_bench.main,
    "async": async_bench.main,
    "obs": obs_bench.main,
    "shift": shift_bench.main,
    "fleet": fleet_bench.main,
    "docs": docs_check.main,
    "fig5": fig5_edc_madc.main,
    "cost": clustering_cost.main,
    "eta_g": eta_g_sweep.main,
    "roofline": roofline.main,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        epilog="BENCH_*.json schema and the >2x regression-gate semantics "
               "are documented in docs/benchmarks.md.")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write every bench's derived metrics to PATH")
    args = ap.parse_args(argv)
    enable_compile_cache()

    names = list(BENCHES) if not args.only else args.only.split(",")
    if args.quick:
        # the CI gate must always exercise the round-executor, round-block,
        # 2-D mesh, population (streamed cohort), robustness (faults /
        # checkpoint / deadline), async (staleness runtime), obs
        # (telemetry overhead), shift (migration efficacy) and fleet
        # (coordinator overhead / kill recovery) suites + the docs check
        for required in ("round_exec", "round_block", "mesh2d",
                         "population", "robustness", "async", "obs",
                         "shift", "fleet", "docs"):
            if required not in names:
                names.append(required)
    # process-default telemetry: trainers/populations the benches build
    # share this tracer (never its registry — repro.obs.from_config), so
    # the report gets the inspector's per-stage breakdown PER BENCH
    tel = obs_telemetry.Telemetry(enabled=True)
    obs_telemetry.set_default(tel)
    print("name,us_per_call,derived")
    rc = 0
    report = {}
    failures = []
    for name in names:
        t0 = time.perf_counter()
        tel.tracer.clear()
        try:
            derived = BENCHES[name](quick=args.quick)
        except Exception as e:  # noqa: BLE001
            print(f"{name},FAILED,{type(e).__name__}: {e}")
            report[name] = {"error": f"{type(e).__name__}: {e}"}
            failures.append((name, "crash", [f"{type(e).__name__}: {e}"]))
            rc = 1
            continue
        us = (time.perf_counter() - t0) * 1e6
        short = ""
        if isinstance(derived, dict):
            short = ";".join(f"{k}={v}" for k, v in list(derived.items())[:3])
            if derived.get("regression"):
                short = "REGRESSION;" + short
                failures.append((name, "perf regression",
                                 derived.get("regression_details")
                                 or ["regression (no details recorded)"]))
                rc = 1
        elif isinstance(derived, list):
            short = f"rows={len(derived)}"
        report[name] = {"us_per_call": us, "derived": derived,
                        "stages": tel.tracer.stage_totals()}
        print(f"{name},{us:.0f},{short}")
    obs_telemetry.set_default(None)
    if failures:
        # per-entry diff instead of a bare nonzero exit: which bench, crash
        # vs watched-metric regression, best recorded value -> measured
        print("\n# GATE FAILURES (schema + gate semantics: "
              "docs/benchmarks.md)")
        for name, kind, details in failures:
            for d in details:
                print(f"  {name} [{kind}]: {d}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, default=str)
            f.write("\n")
        print(f"# wrote {args.json}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
