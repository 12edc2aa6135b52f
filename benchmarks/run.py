"""Benchmark harness: one module per paper table + kernel/engine timing +
the roofline report.  Prints ``name,us_per_call,derived`` CSV and writes
full row dumps to benchmarks/results/*.json.

Run: PYTHONPATH=src python -m benchmarks.run [--only tableN]
"""
import argparse
import json
import pathlib
import sys
import time

RESULTS = pathlib.Path(__file__).resolve().parent / "results"


def _suites():
    from . import (beyond_paper, engine_bench, extra_sweeps,
                   fleet_diurnal_bench, fleet_grid_bench, fleet_sim_bench,
                   fleet_trace_report, kernel_bench, roofline_report,
                   table1_context_law, table2_model_archs,
                   table3_fleet_topology, table4_semantic_routing,
                   table5_gpu_generations, table6_archetypes,
                   table7_power_params, topology_search_bench)
    return {
        # harness_run also records the full-run wall-clock trajectory to
        # results/BENCH_fleet_sim_full.json (the committed quick-config
        # baselines fleet_sim.json / BENCH_fleet_sim.json are refreshed
        # only by a deliberate `fleet_sim_bench.py --quick --json ...
        # --time`; see dump_name below)
        "fleet_sim": fleet_sim_bench.harness_run,
        # Table E sensitivity surface on the compiled engine
        "fleet_grid": fleet_grid_bench.harness_run,
        # searched vs hand-built TopologySpec fleets (optimize_topology);
        # the committed --quick baseline results/topology_search.json is
        # likewise refreshed only by a deliberate bench --quick --json run
        "topology_search": topology_search_bench.harness_run,
        # Table F diurnal day, static vs autoscaled; the committed
        # --quick baseline results/fleet_diurnal.json follows the same
        # deliberate-refresh rule
        "fleet_diurnal": fleet_diurnal_bench.harness_run,
        # FleetScope: Table F cells re-run with detail tracing on —
        # phase-decomposed energy (reconciled <0.1% against the meters),
        # autoscaler ramp lag and peak-window zoom read off the timeline
        "fleet_trace_report": fleet_trace_report.harness_run,
        "table1_context_law": table1_context_law.run,
        "table2_model_archs": table2_model_archs.run,
        "table3_fleet_topology": table3_fleet_topology.run,
        "table4_semantic_routing": table4_semantic_routing.run,
        "table5_gpu_generations": table5_gpu_generations.run,
        "table6_archetypes": table6_archetypes.run,
        "table7_power_params": table7_power_params.run,
        "quantization_sweep": extra_sweeps.quantization,
        "moe_dispatch_sensitivity": extra_sweeps.moe_dispatch,
        "per_arch_one_over_w": extra_sweeps.per_arch_law,
        "beyond_paper": beyond_paper.run,
        "opt_vs_baseline": _opt_vs_baseline,
        "kernel_bench": kernel_bench.run,
        "engine_bench": engine_bench.run,
        "roofline_report": roofline_report.run,
    }


def _opt_vs_baseline():
    from . import opt_vs_baseline
    return opt_vs_baseline.run()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in _suites().items():
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            rows, derived = fn()
        except Exception as e:  # pragma: no cover
            failed.append(name)
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            continue
        us = (time.perf_counter() - t0) * 1e6
        # suites may redirect their generic rows dump (fleet_sim: the
        # harness runs the *full* config, which must never overwrite the
        # committed --quick CI perf-regression baseline fleet_sim.json)
        dump = getattr(fn, "dump_name", name)
        (RESULTS / f"{dump}.json").write_text(json.dumps(rows, indent=1))
        # kernel/engine suites carry their own per-call timings
        if rows and isinstance(rows[0], dict) and "us_per_call" in rows[0]:
            for r in rows:
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        else:
            print(f'{name},{us:.1f},"{derived}"')
    if failed:
        sys.exit(f"FAILED: {failed}")


if __name__ == "__main__":
    main()
