"""Searched vs hand-built topologies (the TopologySpec IR payoff).

`core.topo_search.optimize_topology` searches the declarative topology
space (window ladder depth K, per-rung chip and model, overflow headroom
gamma, prefill/decode disaggregation) for the fleet with the highest
measured-SLO-compliant tok/W.  This bench puts the searched fleet next
to every hand-built §4 topology — homo / two_pool / fleetopt /
multipool(K=3) — on Azure, LMSYS and Agent (Azure only in --quick),
ALL evaluated through the SAME `core.slo.size_to_slo_spec` evaluator
against the SAME frozen arrival trace (common random numbers: the
comparison is topology vs topology, never noise vs noise).

Acceptance gate: on every workload the searched fleet's SLO-compliant
tok/W >= the best hand-built topology's (within 1e-6 — the search is
seeded at multipool K=3, so it can only tie or beat the incumbent).

Rows carry `spec_hash` — the stable TopologySpec hash — which
benchmarks/perf_diff.py folds into the regression-diff cell key, so a
searched topology that *changes shape* shows up as a new cell (and a
missing old one) instead of a silent metric swap.

Standalone:  PYTHONPATH=src python benchmarks/topology_search_bench.py
             [--quick] [--json PATH] [--seed N] [--engine numpy|jax]
Harness:     PYTHONPATH=src python -m benchmarks.run --only topology_search
"""
import json
import sys

from repro.core import ladder_windows
from repro.core.modelspec import LLAMA31_8B, LLAMA31_70B
from repro.core.profiles import H100_LLAMA70B
from repro.core.routing import LONG_WINDOW
from repro.core.slo import SLOSpec, size_to_slo_spec
from repro.core.topo_search import optimize_topology
from repro.core.topospec import TopologySpec
from repro.core.workloads import AGENT, AZURE, LMSYS

# per-workload split boundary (same as fleet_sim_bench)
B_SHORT = {"azure-conv": 4096, "lmsys-chat": 1536, "agent-heavy": 8192}
HAND_BUILT = ("homo", "two_pool", "fleetopt", "multipool")
K_POOLS = 3

# per-kind hand-built spec arguments (kind behaviour itself lives in
# TopologySpec.from_kind — this is just bench argument selection)
_HAND_KW = {"multipool": lambda wl: dict(windows=ladder_windows(K_POOLS))}


def _hand_spec(kind: str, wl) -> TopologySpec:
    kw = _HAND_KW.get(kind, lambda wl: dict(b_short=B_SHORT[wl.name]))(wl)
    return TopologySpec.from_kind(kind, H100_LLAMA70B, LLAMA31_70B, **kw)


def run(slo_requests: int = 3000, seed: int = 0, budget: int = 24,
        quick: bool = False, engine: str = "numpy"):
    from repro.serving.request import sample_trace

    slo = SLOSpec()
    rows = []
    for wl in (AZURE,) if quick else (AZURE, LMSYS, AGENT):
        # ONE frozen trace per workload, shared by every hand-built spec
        # AND the search (every spec's max_window is LONG_WINDOW)
        trace = sample_trace(wl, slo_requests, seed=seed,
                             max_total=LONG_WINDOW)
        best_hand, best_hand_kind = float("-inf"), None
        for kind in HAND_BUILT:
            spec = _hand_spec(kind, wl)
            res = size_to_slo_spec(
                spec, wl, slo=slo, n_requests=slo_requests, seed=seed,
                trim=False, engine=engine, trace=trace)
            score = res.slo_tok_per_watt if res.compliant else 0.0
            if res.compliant and score > best_hand:
                best_hand, best_hand_kind = score, kind
            rows.append(dict(
                table="topology_search", workload=wl.name, topology=kind,
                label=spec.label, spec_hash=spec.spec_hash,
                slo_feasible=round(score, 2),
                measured=round(res.measured_decode_tok_per_watt, 2),
                ttft_p99_s=round(res.ttft_p99_s, 3),
                instances=res.plan.instances, compliant=res.compliant))
        sr = optimize_topology(
            wl, H100_LLAMA70B, LLAMA31_70B, slo=slo,
            small_model=LLAMA31_8B, n_requests=slo_requests, seed=seed,
            budget=budget, trim=False, engine=engine)
        rows.append(dict(
            table="topology_search", workload=wl.name, topology="searched",
            label=sr.best_spec.label, spec_hash=sr.best_spec.spec_hash,
            # same convention as the hand-built rows: a non-compliant
            # fleet's SLO-feasible tok/W is 0, not -inf (keeps the JSON
            # dump strict and the diff cells finite)
            slo_feasible=round(sr.best_score, 2)
            if sr.best_result.compliant else 0.0,
            measured=round(sr.best_result.measured_decode_tok_per_watt, 2),
            ttft_p99_s=round(sr.best_result.ttft_p99_s, 3),
            instances=sr.best_result.plan.instances,
            compliant=sr.best_result.compliant,
            evaluations=sr.evaluations, restarts=sr.restarts,
            best_hand_built=best_hand_kind,
            gain_vs_hand_pct=round(
                100.0 * (sr.best_score / best_hand - 1.0), 1)
            if best_hand > 0 else None))
    searched = {r["workload"]: r for r in rows if r["topology"] == "searched"}
    derived = "; ".join(
        f"{w}: searched {r['slo_feasible']:.2f} tok/W ({r['label']})"
        + (f" vs best hand-built {r['best_hand_built']}"
           f" ({r['gain_vs_hand_pct']:+g}%)"
           if r["best_hand_built"] is not None
           else " (no hand-built topology is SLO-compliant)")
        for w, r in searched.items())
    return rows, derived


def harness_run():
    return run()


# the harness runs the full config; the committed --quick CI baseline
# results/topology_search.json must never be overwritten by it
harness_run.dump_name = "topology_search_full"


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slo-requests", type=int, default=3000)
    ap.add_argument("--budget", type=int, default=24,
                    help="max novel spec evaluations per workload")
    ap.add_argument("--quick", action="store_true",
                    help="Azure-only, 1.5k-request, small-budget smoke (CI)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=("numpy", "jax"), default="numpy")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump {'meta', 'rows'} JSON (perf_diff format)")
    args = ap.parse_args(argv)
    n = 1500 if args.quick else args.slo_requests
    budget = 10 if args.quick else args.budget
    if args.engine == "jax":
        from repro.models.compat import enable_compile_cache
        enable_compile_cache()
    rows, derived = run(slo_requests=n, seed=args.seed, budget=budget,
                        quick=args.quick, engine=args.engine)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"meta": dict(slo_requests=n, budget=budget,
                                    seed=args.seed, quick=args.quick),
                       "rows": rows}, fh, indent=1)

    hdr = (f"{'workload':12s} {'topology':10s} {'spec':30s} {'SLO-ok':>7s}"
           f" {'measured':>8s} {'ttft_p99':>9s} {'inst':>5s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['workload']:12s} {r['topology']:10s} {r['label'][:30]:30s}"
              f" {r['slo_feasible']:7.2f} {r['measured']:8.2f}"
              f" {r['ttft_p99_s']:9.3f} {r['instances']:5d}"
              + ("" if r["compliant"] else "  NON-COMPLIANT"))
    print(derived)

    # acceptance gate: searched >= best hand-built on every workload.
    # A workload where NOTHING complies (agent-heavy at the full config:
    # the 8K+ prompt prefill alone busts the 500 ms TTFT p99 — the SLO
    # is service-time unattainable, cf. DESIGN.md §9) is a reported
    # finding, not a search failure; the gate only fires when the SLO is
    # attainable and the search missed it.
    fails = []
    for wl_name, sr in {r["workload"]: r for r in rows
                        if r["topology"] == "searched"}.items():
        hand = [r["slo_feasible"] for r in rows
                if r["workload"] == wl_name and r["topology"] != "searched"
                and r["compliant"]]
        if not hand and not sr["compliant"]:
            print(f"note: {wl_name}: no topology (hand-built or searched)"
                  f" meets the SLO — service-time unattainable")
        elif not sr["compliant"]:
            fails.append(f"{wl_name}: searched fleet is not SLO-compliant"
                         f" but hand-built {max(hand):.2f} tok/W is")
        elif hand and sr["slo_feasible"] < max(hand) - 1e-6:
            fails.append(f"{wl_name}: searched {sr['slo_feasible']:.2f} <"
                         f" best hand-built {max(hand):.2f}")
    if fails:
        sys.exit("ACCEPTANCE FAIL: " + "; ".join(fails))


if __name__ == "__main__":
    main()
