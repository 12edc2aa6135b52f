"""Analytical vs simulated fleet tok/W (Tables 3/4) + the SLO-constrained
sizing table (the measured side of the paper's own P99 TTFT constraint).

Table A (unconstrained) runs the event-driven fleet simulator
(serving.fleetsim) for every (workload x topology) cell on the calibrated
H100 Llama-70B profile and puts the measured steady-state tok/W next to
the closed-form core.fleet prediction it was provisioned from.
`simulated` is the decode-only measurement (like-for-like with Eq. 4);
`all_in` additionally meters the prefill compute and idle power the
analytical model ignores — the gap is the honest price of serving,
TokenPowerBench-style.

Table B (SLO-constrained) is the bugfix headline: PR 1 showed the fleets
Table A is quoted for *violate* the paper's P99 TTFT <= 500 ms SLO when
actually run.  `core.slo.size_to_slo_spec` re-provisions each topology until
the measured TTFT p99 complies; every Table B cell reports the
SLO-feasible tok/W (the new headline metric next to Eq. 4's unconstrained
number) and its measured TTFT p99 — all <= 0.5 s by construction.  The
sweep covers H100/H200/B200 x homo/fleetopt/multipool(K=3) on Azure, so
the §4.2 generation-gain claim (B200/H100 ~ 1.7x) is re-measured under
the latency constraint.

Table C (disaggregation, §10.3) serves prefill/decode disaggregation
through FleetSim: homo vs fleetopt vs disagg vs disagg+fleetopt on
Azure/H100, analytical (whole-fleet and decode-only) vs measured vs
SLO-constrained, with the KV-handoff energy the interconnect really
charges.  Gates: every disagg cell's measured TTFT p99 <= 500 ms after
size_to_slo_spec; if disagg+fleetopt's measured all-in tok/W falls short of
plain fleetopt's, the bench prints the shortfall and the KV-handoff cost
that (partially) explains it instead of failing.

Table D (model heterogeneity, §5.1/§3.2 — DESIGN.md §9) is the headline
the paper can't give: how much of the semantic-routing and MoE
active-parameter gains survives real queueing, misroutes and the TTFT
SLO.  On H100 it serves homo-70B vs fleetopt-70B vs semantic 8B/70B
(zero misroute, plus the FleetOpt-headroom variant at a 5% classifier
error with its escalation traffic) vs Qwen3-235B-A22B as a `moe_pool` at
dispatch_ms in {0, 2, 10} and as the large model of `moe_semantic` —
analytical vs measured vs SLO-constrained (with the post-compliance trim
phase).  Azure in --quick; Azure + Agent in the full run.  Gate: every
Table D cell is SLO-compliant after size_to_slo_spec.

`--json PATH` dumps {"meta", "rows"} for CI's perf-regression diff
(benchmarks/perf_diff.py --fleet against the committed
benchmarks/results/fleet_sim.json, which is regenerated with
`--quick --json benchmarks/results/fleet_sim.json`).

`--time [PATH]` additionally records per-table and total wall-clock (plus
simulated-seconds-per-wall-second throughput) as
{table, config, wall_s, sim_s_per_wall_s} rows — the repo's perf
trajectory.  Default PATH is benchmarks/results/BENCH_fleet_sim.json (the
committed baseline `perf_diff.py --wall-budget` gates against); CI passes
an explicit scratch path so the baseline is never clobbered in place.

Standalone:  PYTHONPATH=src python benchmarks/fleet_sim_bench.py
             [--n-requests N] [--slo-requests N] [--quick] [--json PATH]
             [--time [PATH]]
Harness:     PYTHONPATH=src python -m benchmarks.run --only fleet_sim
"""
import json
import pathlib
import platform
import sys
import time

from repro.core import TopologySpec, ladder_windows, size_to_slo_spec
from repro.core.hardware import H100
from repro.core.modelspec import LLAMA31_70B, QWEN3_235B_A22B
from repro.core.moe import moe_profile
from repro.core.power import H100_POWER
from repro.core.profiles import (B200_LLAMA70B_FLEET, H100_LLAMA70B,
                                 H200_LLAMA70B)
from repro.core.workloads import AGENT, AZURE, LMSYS
from repro.serving import FleetSim, simulate_spec

BENCH_JSON = pathlib.Path(__file__).resolve().parent / "results" \
    / "BENCH_fleet_sim.json"

# per-workload split boundary (paper: Azure 4K, LMSYS 1.5K, Agent 8K)
B_SHORT = {"azure-conv": 4096, "lmsys-chat": 1536, "agent-heavy": 8192}
TOPOLOGIES = ("homo", "two_pool", "fleetopt")
GENERATIONS = (("H100", H100_LLAMA70B), ("H200", H200_LLAMA70B),
               ("B200", B200_LLAMA70B_FLEET))
SLO_TOPOLOGIES = ("homo", "fleetopt", "multipool")
DISAGG_TOPOLOGIES = ("disagg", "disagg_fleetopt")
K_POOLS = 3
# Table D: MoE expert-dispatch sweep and the semantic classifier error
# whose misrouted-giant-prompt tail still fits the 1% p99 TTFT budget
# (at 0.1 on Azure the misroutes alone are ~1.1% of traffic and the SLO
# is service-time unattainable — DESIGN.md §9)
MOE_DISPATCH_MS = (0.0, 2.0, 10.0)
D_MISROUTE = 0.05


def disagg_vs_fleetopt(rows):
    """(disagg rows, unconstrained Azure rows) keyed by topology — the one
    place the Table C comparison cells are looked up (run() derives the
    acceptance ratio from them, main() prints the verdict)."""
    dis = {r["topology"]: r for r in rows if r["table"] == "disagg"}
    az_a = {r["topology"]: r for r in rows
            if r["table"] == "unconstrained"
            and r.get("workload") == "azure-conv"}
    return dis, az_a


def _table_d_cells(wl):
    """(kind, profile, model, kwargs) per Table D cell for one workload."""
    bs = B_SHORT[wl.name]
    moe = moe_profile(QWEN3_235B_A22B, H100, H100_POWER, tp=8)
    cells = [("homo", H100_LLAMA70B, LLAMA31_70B, {}),
             ("fleetopt", H100_LLAMA70B, LLAMA31_70B, dict(b_short=bs)),
             ("semantic", H100_LLAMA70B, LLAMA31_70B, dict(b_short=bs)),
             ("semantic_fleetopt", H100_LLAMA70B, LLAMA31_70B,
              dict(b_short=bs, misroute_rate=D_MISROUTE))]
    cells += [("moe_pool", moe, QWEN3_235B_A22B, dict(dispatch_ms=d))
              for d in MOE_DISPATCH_MS]
    cells.append(("moe_semantic", moe, QWEN3_235B_A22B,
                  dict(b_short=bs, misroute_rate=D_MISROUTE,
                       dispatch_ms=2.0)))
    return cells


def table_d(workloads, *, n_requests: int, slo_requests: int, seed: int,
            engine: str = "numpy"):
    """Model-heterogeneous cells: measured + SLO-constrained, per workload."""
    rows = []
    for wl in workloads:
        for kind, prof, mdl, kw in _table_d_cells(wl):
            spec = TopologySpec.from_kind(kind, prof, mdl,
                                          misroute_seed=seed, **kw)
            cell = simulate_spec(spec, wl, n_requests=n_requests, seed=seed,
                                 engine=engine)
            res = size_to_slo_spec(spec, wl, n_requests=slo_requests,
                                   seed=seed, engine=engine)
            f = cell.report["fleet"]
            rows.append(dict(
                table="model_hetero", workload=wl.name, topology=kind,
                model=mdl.name,
                dispatch_ms=float(kw.get("dispatch_ms", 0.0)),
                misroute_rate=float(kw.get("misroute_rate", 0.0)),
                analytical=round(cell.analytical_tok_per_watt, 2),
                simulated=round(cell.sim_decode_tok_per_watt, 2),
                delta_pct=round(cell.delta_pct, 1),
                all_in=round(cell.sim_tok_per_watt, 2),
                ttft_p99_s=f.get("ttft_p99_s", 0.0),
                escalations=f["escalations"], migrations=f["migrations"],
                dispatch_energy_frac=f["moe_dispatch_energy_frac"],
                slo_feasible=round(res.slo_tok_per_watt, 2),
                slo_measured_all_in=round(res.measured_tok_per_watt, 2),
                slo_ttft_p99_s=round(res.ttft_p99_s, 3),
                slo_added=res.instances_added,
                slo_trimmed=res.instances_trimmed,
                slo_compliant=res.compliant))
    return rows


# per-kind bench arguments (kind *behaviour* lives in
# core.topospec.TopologySpec.from_kind; this is just argument selection)
_SLO_CELL_KW = {"multipool": lambda: dict(windows=ladder_windows(K_POOLS))}


def _slo_cell(kind: str, profile, *, n_requests: int, seed: int,
              engine: str = "numpy"):
    kw = _SLO_CELL_KW.get(
        kind, lambda: dict(b_short=B_SHORT[AZURE.name]))()
    spec = TopologySpec.from_kind(kind, profile, LLAMA31_70B,
                                  misroute_seed=seed, **kw)
    return size_to_slo_spec(spec, AZURE, n_requests=n_requests, seed=seed,
                            engine=engine)


class _TableTimer:
    """Per-table wall-clock + simulated-seconds throughput recorder —
    the bench's perf-trajectory rows ({table, config, wall_s,
    sim_s_per_wall_s})."""

    def __init__(self, config: dict):
        self.config = config
        self.rows = []
        self._t0 = time.perf_counter()
        self._wall0 = self._t0
        self._sim0 = FleetSim.sim_seconds_total
        self._simstart = self._sim0

    def lap(self, table: str) -> None:
        now, sim = time.perf_counter(), FleetSim.sim_seconds_total
        wall = now - self._t0
        self.rows.append(dict(
            table=table, config=self.config, wall_s=round(wall, 3),
            sim_s_per_wall_s=round((sim - self._sim0) / wall, 1)
            if wall > 0 else 0.0))
        self._t0, self._sim0 = now, sim

    def total(self) -> None:
        wall = time.perf_counter() - self._wall0
        sim = FleetSim.sim_seconds_total - self._simstart
        self.rows.append(dict(
            table="total", config=self.config, wall_s=round(wall, 3),
            sim_s_per_wall_s=round(sim / wall, 1) if wall > 0 else 0.0))


def run(n_requests: int = 10_000, slo_requests: int = 3000, seed: int = 0,
        quick: bool = False, engine: str = "numpy"):
    timer = _TableTimer(dict(quick=quick, n_requests=n_requests,
                             slo_requests=slo_requests, seed=seed))
    rows = []
    for wl in (AZURE, LMSYS, AGENT):
        for kind in TOPOLOGIES:
            spec = TopologySpec.from_kind(
                kind, H100_LLAMA70B, LLAMA31_70B, b_short=B_SHORT[wl.name],
                misroute_seed=seed)
            cell = simulate_spec(spec, wl, n_requests=n_requests, seed=seed,
                                 engine=engine)
            f = cell.report["fleet"]
            rows.append(dict(cell.row(), table="unconstrained",
                             occupancy={r: s["occupancy"]
                                        for r, s in cell.report.items()
                                        if r != "fleet"},
                             prefill_energy_frac=f["prefill_energy_frac"],
                             tokens_per_s=f["tokens_per_s"]))
    timer.lap("unconstrained")
    slo = {}
    for gen, prof in GENERATIONS:
        for kind in SLO_TOPOLOGIES:
            res = _slo_cell(kind, prof, n_requests=slo_requests,
                            seed=seed, engine=engine)
            slo[(gen, kind)] = res
            rows.append(dict(res.row(), table="slo", generation=gen))
    timer.lap("slo")
    # Table C: disaggregation on Azure/H100 (homo/fleetopt cells reuse
    # Table A measured + Table B SLO numbers; only the disagg kinds add
    # simulation + SLO-loop work)
    for kind in DISAGG_TOPOLOGIES:
        spec = TopologySpec.from_kind(
            kind, H100_LLAMA70B, LLAMA31_70B, b_short=B_SHORT[AZURE.name],
            misroute_seed=seed)
        cell = simulate_spec(spec, AZURE, n_requests=n_requests, seed=seed,
                             engine=engine)
        res = size_to_slo_spec(spec, AZURE, n_requests=slo_requests,
                               seed=seed, engine=engine)
        f = cell.report["fleet"]
        rows.append(dict(
            table="disagg", workload=AZURE.name, topology=kind,
            analytical=round(cell.analytical_tok_per_watt, 2),
            analytical_fleet=round(cell.analytical_fleet_tok_per_watt, 2),
            simulated=round(cell.sim_decode_tok_per_watt, 2),
            delta_pct=round(cell.delta_pct, 1),
            all_in=round(cell.sim_tok_per_watt, 2),
            ttft_p99_s=f.get("ttft_p99_s", 0.0),
            handoffs=f["handoffs"], migrations=f["migrations"],
            kv_handoff_joules=f["kv_handoff_joules"],
            kv_handoff_energy_frac=f["kv_handoff_energy_frac"],
            slo_feasible=round(res.slo_tok_per_watt, 2),
            slo_measured_all_in=round(res.measured_tok_per_watt, 2),
            slo_ttft_p99_s=round(res.ttft_p99_s, 3),
            slo_added=res.instances_added,
            slo_compliant=res.compliant))
    timer.lap("disagg")
    # Table D: model heterogeneity (Azure always; Agent in the full run)
    rows += table_d((AZURE,) if quick else (AZURE, AGENT),
                    n_requests=n_requests, slo_requests=slo_requests,
                    seed=seed, engine=engine)
    timer.lap("model_hetero")
    az = {r["topology"]: r["simulated"] for r in rows
          if r.get("workload") == "azure-conv"
          and r["table"] == "unconstrained"}
    ratio = az["fleetopt"] / az["homo"] if az["homo"] else float("nan")
    slo_ratio = (slo[("H100", "fleetopt")].slo_tok_per_watt
                 / slo[("H100", "homo")].slo_tok_per_watt)
    gen_gain = {k: (slo[("B200", k)].slo_tok_per_watt
                    / slo[("H100", k)].slo_tok_per_watt)
                for k in SLO_TOPOLOGIES}
    dis, az_a = disagg_vs_fleetopt(rows)
    dfo, fo = dis["disagg_fleetopt"]["all_in"], az_a["fleetopt"]["all_in"]
    dh = {(r["workload"], r["topology"], r["dispatch_ms"]): r for r in rows
          if r["table"] == "model_hetero"}
    d_homo = dh[("azure-conv", "homo", 0.0)]
    moe_adv = {d: dh[("azure-conv", "moe_pool", d)]["simulated"]
               / d_homo["simulated"] for d in MOE_DISPATCH_MS}
    sem_adv = dh[("azure-conv", "semantic", 0.0)]["simulated"] \
        / d_homo["simulated"]
    derived = (f"simulated fleetopt/homo on Azure = {ratio:.2f}x "
               f"(acceptance >= 2x); SLO-constrained = {slo_ratio:.2f}x; "
               f"B200/H100 gain under SLO: "
               + ", ".join(f"{k} {v:.2f}x" for k, v in gen_gain.items())
               + f"; disagg+fleetopt/fleetopt all-in = {dfo / fo:.2f}x"
               + f"; measured semantic/homo = {sem_adv:.2f}x"
               + "; measured MoE/homo at dispatch "
               + ", ".join(f"{d:g}ms {v:.2f}x" for d, v in moe_adv.items()))
    timer.total()
    return rows, derived, timer.rows


def write_bench_json(timings, path=BENCH_JSON) -> None:
    """Persist the perf-trajectory rows ({table, config, wall_s,
    sim_s_per_wall_s}) with enough host metadata to judge whether a
    wall-clock delta is a code change or a runner-class change."""
    pathlib.Path(path).parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"meta": dict(python=platform.python_version(),
                                machine=platform.machine(),
                                system=platform.system()),
                   "timings": timings}, fh, indent=1)


def harness_run():
    """benchmarks.run entry point: (rows, derived) like every suite, with
    the timing rows persisted as a side effect — the full-run perf
    trajectory.  Written next to (never over) the committed quick-config
    baseline BENCH_fleet_sim.json, which only a deliberate
    `--quick --time` refresh may move: the CI wall-budget gate compares
    quick against quick."""
    rows, derived, timings = run()
    write_bench_json(timings, BENCH_JSON.with_name("BENCH_fleet_sim_full"
                                                   ".json"))
    return rows, derived


# redirect benchmarks.run's generic rows dump away from the committed
# --quick CI baseline results/fleet_sim.json (full-config rows are not
# comparable cell-for-cell with the quick gate's)
harness_run.dump_name = "fleet_sim_full"


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-requests", type=int, default=10_000)
    ap.add_argument("--slo-requests", type=int, default=3000)
    ap.add_argument("--quick", action="store_true",
                    help="1k-request (1.5k SLO) smoke run (CI)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=("numpy", "jax"), default="numpy",
                    help="pool drive loop: the numpy oracle (default) or "
                         "the compiled serving.jax_engine drains — same "
                         "cells, same tolerances (CI diffs jax against "
                         "the committed numpy baseline)")
    ap.add_argument("--trace", metavar="PATH", nargs="?", default=None,
                    const="-",
                    help="record a FleetScope lifecycle trace of every "
                         "sim in the run (FleetSim.default_telemetry); "
                         "optional PATH dumps it as Perfetto-viewable "
                         "Chrome trace-event JSON.  Rows are unchanged "
                         "— the CI wall-budget gate runs with this on "
                         "to price the tracing overhead")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump {'meta', 'rows'} JSON (the CI perf-"
                         "regression baseline/current format)")
    ap.add_argument("--time", metavar="PATH", nargs="?", default=None,
                    const=str(BENCH_JSON),
                    help="record per-table + total wall-clock to PATH "
                         f"(default {BENCH_JSON}; gated in CI by "
                         "perf_diff.py --wall-budget)")
    args = ap.parse_args(argv)
    n = 1000 if args.quick else args.n_requests
    n_slo = 1500 if args.quick else args.slo_requests
    recorder = None
    if args.trace:
        from repro.serving import TraceRecorder, to_perfetto
        recorder = TraceRecorder(level="lifecycle")
        FleetSim.default_telemetry = recorder
    if args.engine == "jax":
        from repro.models.compat import enable_compile_cache
        enable_compile_cache()
    rows, derived, timings = run(n_requests=n, slo_requests=n_slo,
                                 seed=args.seed, quick=args.quick,
                                 engine=args.engine)
    if recorder is not None:
        FleetSim.default_telemetry = None
        counts = {k: v for k, v in recorder.counts().items() if v}
        print(f"=== trace: {len(recorder.events)} events over "
              f"{len(recorder.pool_names)} pools {counts} ===")
        if args.trace != "-":
            with open(args.trace, "w") as fh:
                json.dump(to_perfetto(recorder), fh)
            print(f"perfetto trace -> {args.trace}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"meta": dict(n_requests=n, slo_requests=n_slo,
                                    seed=args.seed, quick=args.quick),
                       "rows": rows}, fh, indent=1)
    if args.time:
        write_bench_json(timings, args.time)
        print("=== wall-clock (s) ===")
        for t in timings:
            print(f"{t['table']:14s} {t['wall_s']:8.2f}"
                  f"  ({t['sim_s_per_wall_s']:.0f} sim-s/wall-s)")

    print("=== Table A: unconstrained (H100) ===")
    hdr = (f"{'workload':12s} {'topology':9s} {'analytic':>8s} {'simulated':>9s}"
           f" {'delta%':>7s} {'all-in':>7s} {'ttft_p99':>9s} {'migr':>5s}")
    print(hdr)
    print("-" * len(hdr))
    uncon = [r for r in rows if r["table"] == "unconstrained"]
    for r in uncon:
        print(f"{r['workload']:12s} {r['topology']:9s} {r['analytical']:8.2f} "
              f"{r['simulated']:9.2f} {r['delta_pct']:7.1f} {r['all_in']:7.2f} "
              f"{r['ttft_p99_s']:9.2f} {r['migrations']:5d}")

    print("\n=== Table B: SLO-constrained (Azure, P99 TTFT <= 500 ms) ===")
    hdr = (f"{'gen':5s} {'topology':9s} {'Eq.4':>7s} {'SLO-ok':>7s}"
           f" {'cost%':>6s} {'measured':>8s} {'ttft_p99':>9s} {'inst':>5s}"
           f" {'+add':>5s} {'rds':>4s}")
    print(hdr)
    print("-" * len(hdr))
    slo_rows = [r for r in rows if r["table"] == "slo"]
    for r in slo_rows:
        print(f"{r['generation']:5s} {r['topology']:9s}"
              f" {r['unconstrained']:7.2f} {r['slo_feasible']:7.2f}"
              f" {r['cost_pct']:6.1f} {r['measured']:8.2f}"
              f" {r['ttft_p99_s']:9.3f} {r['instances']:5d}"
              f" {r['added']:5d} {r['rounds']:4d}"
              + ("" if r["compliant"] else "  NON-COMPLIANT"))

    print("\n=== Table C: prefill/decode disaggregation (Azure, H100) ===")
    dis, az_a = disagg_vs_fleetopt(rows)
    slo_b = {r["topology"]: r for r in slo_rows
             if r["generation"] == "H100"}
    dis_rows = list(dis.values())
    hdr = (f"{'topology':16s} {'an.fleet':>8s} {'an.dec':>7s} {'simul':>7s}"
           f" {'all-in':>7s} {'SLO-ok':>7s} {'ttft(SLO)':>10s}"
           f" {'kvJ':>8s} {'hoffs':>6s}")
    print(hdr)
    print("-" * len(hdr))
    for kind in ("homo", "fleetopt"):
        a, b = az_a[kind], slo_b[kind]
        print(f"{kind:16s} {a['analytical']:8.2f} {a['analytical']:7.2f}"
              f" {a['simulated']:7.2f} {a['all_in']:7.2f}"
              f" {b['slo_feasible']:7.2f} {b['ttft_p99_s']:10.3f}"
              f" {'-':>8s} {'-':>6s}")
    for kind in ("disagg", "disagg_fleetopt"):
        r = dis[kind]
        print(f"{kind:16s} {r['analytical_fleet']:8.2f}"
              f" {r['analytical']:7.2f} {r['simulated']:7.2f}"
              f" {r['all_in']:7.2f} {r['slo_feasible']:7.2f}"
              f" {r['slo_ttft_p99_s']:10.3f}"
              f" {r['kv_handoff_joules']:8.1f} {r['handoffs']:6d}"
              + ("" if r["slo_compliant"] else "  NON-COMPLIANT"))
    print("\n=== Table D: model heterogeneity (H100, semantic + MoE) ===")
    hdr = (f"{'workload':12s} {'topology':17s} {'model':16s} {'disp':>5s}"
           f" {'misr':>5s} {'analytic':>8s} {'simul':>7s} {'all-in':>7s}"
           f" {'SLO-ok':>7s} {'ttft(SLO)':>10s} {'esc':>5s} {'trim':>5s}")
    print(hdr)
    print("-" * len(hdr))
    het_rows = [r for r in rows if r["table"] == "model_hetero"]
    for r in het_rows:
        print(f"{r['workload']:12s} {r['topology']:17s}"
              f" {r['model'][:16]:16s} {r['dispatch_ms']:5.0f}"
              f" {r['misroute_rate']:5.2f} {r['analytical']:8.2f}"
              f" {r['simulated']:7.2f} {r['all_in']:7.2f}"
              f" {r['slo_feasible']:7.2f} {r['slo_ttft_p99_s']:10.3f}"
              f" {r['escalations']:5d} {r['slo_trimmed']:5d}"
              + ("" if r["slo_compliant"] else "  NON-COMPLIANT"))

    dfo, fo = dis["disagg_fleetopt"]["all_in"], az_a["fleetopt"]["all_in"]
    if dfo >= fo:
        print(f"measured: disagg+fleetopt all-in tok/W beats interleaved "
              f"fleetopt ({dfo:.2f} vs {fo:.2f}, +{100 * (dfo / fo - 1):.1f}%)"
              f" — prefill interference removed from the decode pools")
    else:
        r = dis["disagg_fleetopt"]
        print(f"measured: disagg+fleetopt all-in tok/W falls short of "
              f"interleaved fleetopt ({dfo:.2f} vs {fo:.2f}, "
              f"{100 * (dfo / fo - 1):.1f}%) — the dedicated prefill fleet "
              f"burns saturated watts the interleave absorbed; KV handoff "
              f"adds {r['kv_handoff_joules']:.1f} J "
              f"({100 * r['kv_handoff_energy_frac']:.3f}% of fleet energy)")
    print(derived)

    # acceptance gates -----------------------------------------------------
    fails = []
    az = {r["topology"]: r["simulated"] for r in uncon
          if r["workload"] == "azure-conv"}
    if az["fleetopt"] < 2.0 * az["homo"]:
        fails.append("simulated fleetopt < 2x homo on Azure")
    bad = [f"{r['generation']}/{r['topology']}" for r in slo_rows
           if not r["compliant"] or r["ttft_p99_s"] > 0.5]
    if bad:
        fails.append(f"SLO cells non-compliant: {bad}")
    slo_az = {(r["generation"], r["topology"]): r["slo_feasible"]
              for r in slo_rows}
    if slo_az[("H100", "fleetopt")] < 2.0 * slo_az[("H100", "homo")]:
        fails.append("SLO-constrained fleetopt < 2x homo on Azure (H100)")
    bad_dis = [r["topology"] for r in dis_rows
               if not r["slo_compliant"] or r["slo_ttft_p99_s"] > 0.5]
    if bad_dis:
        fails.append(f"disagg cells violate the TTFT SLO after"
                     f" size_to_slo_spec: {bad_dis}")
    bad_het = [f"{r['workload']}/{r['topology']}@d{r['dispatch_ms']:g}"
               for r in het_rows
               if not r["slo_compliant"] or r["slo_ttft_p99_s"] > 0.5]
    if bad_het:
        fails.append(f"Table D cells violate the TTFT SLO after"
                     f" size_to_slo_spec: {bad_het}")
    if fails:
        sys.exit("ACCEPTANCE FAIL: " + "; ".join(fails))


if __name__ == "__main__":
    main()
