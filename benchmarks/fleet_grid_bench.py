"""Table E: dense Azure sensitivity surfaces around the paper's headline
claims (ROADMAP's top open item), measured through the fleet simulator.

Every headline number the repo reproduces — FleetOpt ~2.5x, the B200/H100
generation gain, the semantic-routing and MoE active-parameter advantages —
is a single cell.  Table E measures its *neighborhood*: 260 cells over
misroute_rate x dispatch_ms x chip generation x pool-count K (plus the
b_short/gamma split-boundary axes FleetOpt is sensitive to), so a claim
like "semantic routing wins 3x" comes with the classifier-error rate at
which it stops being true and the dispatch floor at which the MoE bound
collapses, on every chip generation at once.

The grid is unaffordable with the numpy engine driving every cell
(~0.7 s/cell serial); it exists because `serving.jax_engine` drains each
scenario stage as one jitted XLA program whose event-free spans fast-
forward in closed form, and `serving.run_fleet_grid` batches the drains of
many prepared scenarios per topological stage.  All cells share one seeded
Azure trace (common random numbers), so cross-cell differences are pure
config effects, not sampling noise — which is what lets a modest
n_requests trace out a smooth surface.

Cell families (workload: Azure; 4 chips H100/H200/B200/GB200):

  moe_semantic       misroute(6) x dispatch_ms(5) x chip(4)      = 120
  semantic_fleetopt  misroute(6) x b_short(3)     x chip(4)      =  72
  fleetopt           gamma(3)    x b_short(3)     x chip(4)      =  36
  moe_pool           dispatch_ms(5)               x chip(4)      =  20
  multipool          K(3)                         x chip(4)      =  12

Acceptance: the full 260-cell grid completes in no more wall-clock than
the committed --quick fleet_sim bench budget
(benchmarks/results/BENCH_fleet_sim.json total) — the bench prints the
verdict against that number.

`--json PATH` dumps {"meta", "rows"}; the harness dump goes to
benchmarks/results/fleet_grid.json — never the perf-regression gate's
fleet_sim.json.  `--time [PATH]` records per-family wall-clock to
benchmarks/results/BENCH_fleet_grid.json (again: never the committed
BENCH_fleet_sim.json the CI wall gate reads).

Standalone:  PYTHONPATH=src python benchmarks/fleet_grid_bench.py
             [--n-requests N] [--seed S] [--engine jax|numpy]
             [--width W] [--json PATH] [--time [PATH]]
Harness:     PYTHONPATH=src python -m benchmarks.run --only fleet_grid
"""
import json
import sys

from repro.core.hardware import B200, GB200, H100, H200
from repro.core.modelspec import LLAMA31_70B, QWEN3_235B_A22B
from repro.core.moe import moe_profile
from repro.core.multipool import ladder_windows
from repro.core.power import B200_POWER, GB200_POWER, H100_POWER, H200_POWER
from repro.core.profiles import (B200_LLAMA70B_FLEET, GB200_LLAMA70B,
                                 H100_LLAMA70B, H200_LLAMA70B)
from repro.core.topospec import TopologySpec
from repro.core.workloads import AZURE
from repro.models.compat import enable_compile_cache
from repro.serving import prepare_spec, run_fleet_grid

from .fleet_sim_bench import BENCH_JSON, _TableTimer, write_bench_json

GRID_BENCH_JSON = BENCH_JSON.with_name("BENCH_fleet_grid.json")

CHIPS = (("H100", H100, H100_POWER, H100_LLAMA70B),
         ("H200", H200, H200_POWER, H200_LLAMA70B),
         ("B200", B200, B200_POWER, B200_LLAMA70B_FLEET),
         ("GB200", GB200, GB200_POWER, GB200_LLAMA70B))
MISROUTES = (0.0, 0.02, 0.05, 0.08, 0.10, 0.15)
DISPATCH_MS = (0.0, 1.0, 2.0, 5.0, 10.0)
B_SHORTS = (2048, 4096, 8192)
GAMMAS = (1.5, 2.0, 3.0)
K_POOLS = (2, 3, 4)
# cells drained per run_fleet_grid call: XLA:CPU is memory-bound, so wide
# vmap batches pay more per iteration than they amortize — small groups
# just cap padding waste and per-call dispatch overhead
DEFAULT_WIDTH = 4
DEFAULT_N_REQUESTS = 400

# (row_floor, n_slots, queue) padding classes for the compiled drains.
# The grid's 260 cells span 66 natural power-of-two pool shapes, and on
# the single-core CI runner every distinct shape costs a ~2 s XLA build —
# an order of magnitude more than actually *running* the warmed program —
# so each pool joins the cheapest class below that fits its (S, Q), the
# class's pools concatenate along the instance axis (`jax_engine` keeps
# per-pool constants in (I,) rows, so instance counts never pad), and the
# whole grid reuses ~9 compiled programs.  The list is *tuned*, not
# hand-drawn: a drain-call composition log over every cell at the default
# n_requests feeds a local search minimizing (signatures x build cost +
# padded elements x measured per-element-iteration cost) — signature
# count and padding waste pull in opposite directions, and the optimum
# sits at ~4x padded-over-actual across the whole grid (the old
# hand-picked list sat at ~15x, which made the *warm* executions, not
# the compiles, the grid's bottleneck).  The row floor rounds a chunk's
# summed instance count up so mixtures land on few signatures; a pool
# that outgrows every class (larger --n-requests fattening queues) falls
# back to its natural buckets — correct, just one extra compile.
SHAPE_CLASSES = ((256, 32, 4),      # MoE expert pools, tiny slots/queues
                 (128, 48, 24),     # tail stages: second/overflow pools
                 (128, 96, 24),     # small dense pools
                 (64, 256, 64),     # semantic/16K first pools
                 (32, 768, 96),     # fleetopt short pools, 8K ladder
                 (8, 1536, 96))     # b_short=2048 / 4K-ladder slot monsters


def grid_cells():
    """(row-label dict, kind, profile, model, prepare kwargs) per cell."""
    cells = []
    for gen, chip, power, prof in CHIPS:
        moe = moe_profile(QWEN3_235B_A22B, chip, power, tp=8)

        def cell(kind, profile, model, **kw):
            cells.append((dict(table="grid", generation=gen,
                               workload=AZURE.name, topology=kind,
                               model=model.name,
                               dispatch_ms=float(kw.get("dispatch_ms", 0.0)),
                               misroute_rate=float(
                                   kw.get("misroute_rate", 0.0)),
                               b_short=int(kw.get("b_short", 0)),
                               gamma=float(kw.get("gamma", 0.0)),
                               k_pools=len(kw.get("windows", ()))),
                          kind, profile, model, kw))

        for mr in MISROUTES:
            for d in DISPATCH_MS:
                cell("moe_semantic", moe, QWEN3_235B_A22B, b_short=4096,
                     misroute_rate=mr, dispatch_ms=d)
            for bs in B_SHORTS:
                cell("semantic_fleetopt", prof, LLAMA31_70B, b_short=bs,
                     misroute_rate=mr)
        for g in GAMMAS:
            for bs in B_SHORTS:
                cell("fleetopt", prof, LLAMA31_70B, b_short=bs, gamma=g)
        for d in DISPATCH_MS:
            cell("moe_pool", moe, QWEN3_235B_A22B, dispatch_ms=d)
        for k in K_POOLS:
            cell("multipool", prof, LLAMA31_70B,
                 windows=ladder_windows(k))
    return cells


def run(n_requests: int = DEFAULT_N_REQUESTS, seed: int = 0,
        engine: str = "jax", width: int = DEFAULT_WIDTH):
    if engine == "jax":
        # the handful of drain programs compile once per cache directory
        enable_compile_cache()
    cells = grid_cells()
    timer = _TableTimer(dict(n_requests=n_requests, seed=seed,
                             engine=engine, width=width))
    rows = []
    by_family = {}
    for label, kind, prof, mdl, kw in cells:
        by_family.setdefault(kind, []).append((label, kind, prof, mdl, kw))
    for family, fam_cells in by_family.items():
        for i in range(0, len(fam_cells), max(width, 1)):
            chunk = fam_cells[i:i + max(width, 1)]
            scenarios = [prepare_spec(
                TopologySpec.from_kind(kind, prof, mdl, misroute_seed=seed,
                                       **kw),
                AZURE, n_requests=n_requests, seed=seed, engine=engine)
                for _, kind, prof, mdl, kw in chunk]
            floors = SHAPE_CLASSES if engine == "jax" else None
            for (label, *_), cell in zip(
                    chunk, run_fleet_grid(scenarios, pad_floors=floors)):
                f = cell.report["fleet"]
                rows.append(dict(
                    label,
                    analytical=round(cell.analytical_tok_per_watt, 3),
                    simulated=round(cell.sim_decode_tok_per_watt, 3),
                    all_in=round(cell.sim_tok_per_watt, 3),
                    delta_pct=round(cell.delta_pct, 1),
                    completed=f["completed"],
                    escalations=f["escalations"],
                    migrations=f["migrations"]))
        timer.lap(family)
    timer.total()
    return rows, derive(rows), timer.rows


def _by(rows, **match):
    out = [r for r in rows
           if all(r.get(k) == v for k, v in match.items())]
    assert out, match
    return out


def derive(rows) -> str:
    """Sensitivity one-liners: each headline claim with its measured
    neighborhood boundaries."""
    fo = {(r["generation"], r["gamma"], r["b_short"]): r["simulated"]
          for r in _by(rows, topology="fleetopt")}
    gain = [fo[("B200", g, b)] / fo[("H100", g, b)]
            for g in GAMMAS for b in B_SHORTS]
    # misroute rate at which the semantic split stops beating plain
    # fleetopt (same chip, the paper's 4K boundary)
    fo_ref = fo[("H100", 2.0, 4096)]
    sem = sorted((r["misroute_rate"], r["simulated"]) for r in
                 _by(rows, topology="semantic_fleetopt",
                     generation="H100", b_short=4096))
    crossover = next((mr for mr, v in sem if v < fo_ref), None)
    cross_txt = f">{sem[-1][0]:g}" if crossover is None else f"{crossover:g}"
    moe = {(r["generation"], r["dispatch_ms"]): r["simulated"]
           for r in _by(rows, topology="moe_pool")}
    slope = moe[("H100", DISPATCH_MS[-1])] / moe[("H100", 0.0)]
    mp = {(r["generation"], r["k_pools"]): r["simulated"]
          for r in _by(rows, topology="multipool")}
    best_k = {gen: max(K_POOLS, key=lambda k: mp[(gen, k)])
              for gen, *_ in CHIPS}
    return (f"B200/H100 fleetopt gain across gamma x b_short: "
            f"{min(gain):.2f}-{max(gain):.2f}x; "
            f"semantic_fleetopt(H100,4K) falls below fleetopt at misroute "
            f"{cross_txt}; "
            f"MoE tok/W at {DISPATCH_MS[-1]:g}ms dispatch = {slope:.2f}x "
            f"of 0ms; best K per chip: "
            + ", ".join(f"{g}={k}" for g, k in best_k.items()))


def harness_run():
    """benchmarks.run entry point (rows, derived)."""
    rows, derived, timings = run(engine="jax")
    write_bench_json(timings, GRID_BENCH_JSON.with_name(
        "BENCH_fleet_grid_full.json"))
    return rows, derived


# keep the generic rows dump away from every committed perf baseline
harness_run.dump_name = "fleet_grid"


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-requests", type=int, default=DEFAULT_N_REQUESTS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=("jax", "numpy"), default="jax")
    ap.add_argument("--width", type=int, default=DEFAULT_WIDTH,
                    help="scenarios per batched drain call")
    ap.add_argument("--json", metavar="PATH", default=None)
    ap.add_argument("--time", metavar="PATH", nargs="?", default=None,
                    const=str(GRID_BENCH_JSON))
    args = ap.parse_args(argv)
    rows, derived, timings = run(n_requests=args.n_requests, seed=args.seed,
                                 engine=args.engine, width=args.width)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"meta": dict(n_requests=args.n_requests,
                                    seed=args.seed, engine=args.engine,
                                    width=args.width), "rows": rows}, fh,
                      indent=1)
    if args.time:
        write_bench_json(timings, args.time)

    print(f"=== Table E: Azure sensitivity grid ({len(rows)} cells) ===")
    hdr = (f"{'topology':17s} {'gen':6s} {'misr':>5s} {'disp':>5s}"
           f" {'b_short':>7s} {'gamma':>5s} {'K':>2s} {'analytic':>8s}"
           f" {'simul':>7s} {'all-in':>7s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['topology']:17s} {r['generation']:6s}"
              f" {r['misroute_rate']:5.2f} {r['dispatch_ms']:5.1f}"
              f" {r['b_short']:7d} {r['gamma']:5.2f} {r['k_pools']:2d}"
              f" {r['analytical']:8.2f} {r['simulated']:7.2f}"
              f" {r['all_in']:7.2f}")
    for t in timings:
        print(f"[time] {t['table']:18s} {t['wall_s']:8.2f}s"
              f"  ({t['sim_s_per_wall_s']:.0f} sim-s/wall-s)")
    print(derived)

    # acceptance: the full grid must fit inside the committed --quick
    # fleet_sim bench wall budget (the surface is only useful if it can
    # be re-measured as casually as the headline tables)
    fails = []
    incomplete = [r for r in rows if r["completed"] != n_expected(args)]
    if incomplete:
        fails.append(f"{len(incomplete)} cells dropped requests "
                     f"(first: {incomplete[0]})")
    if BENCH_JSON.exists():
        budget = [t["wall_s"] for t in
                  json.loads(BENCH_JSON.read_text())["timings"]
                  if t["table"] == "total"][-1]
        wall = [t["wall_s"] for t in timings if t["table"] == "total"][-1]
        verdict = "within" if wall <= budget else "OVER"
        print(f"grid wall-clock {wall:.1f}s vs --quick bench budget "
              f"{budget:.1f}s: {verdict}")
        if wall > budget:
            fails.append(f"grid {wall:.1f}s exceeds the --quick bench "
                         f"budget {budget:.1f}s")
    if fails:
        sys.exit("ACCEPTANCE FAIL: " + "; ".join(fails))


def n_expected(args) -> int:
    return args.n_requests


if __name__ == "__main__":
    main()
