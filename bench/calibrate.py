"""Readings the comparison's limits are set from, for one cell.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--controls float32,altered,...] [--control-seeds 1,2,3]

In one process (one compile per signature): for every seed, one call of the
cell's unit of work (on the seed's first deal, as a run's warm-up drains
it) on the system under test as it stands, and one with
each control of `bench.controls` in place (on the control seeds), each
compared with the plain reference on the same triples.  Prints one JSON
line per reading: {"seed", "control" (null for the sound program), the
compared numbers, each scenario's overflow migrations}.  Not part of a
benchmark run; needs the same chip as one.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def worst_meters(got, ref) -> dict:
    """Per pool and meter, worst scenario: `meter_rel`'s gap, and the gap
    over the instance's own value of that meter, for reading where a gap
    comes from."""
    from bench import compare
    import numpy as np
    out = {}
    for g, r in zip(got, ref):
        for role in r["order"]:
            pg, pr = g["pools"][role], r["pools"][role]
            for k, v in compare.meter_gaps(pg, pr).items():
                own = compare._gap(pg["floats"][k], pr["floats"][k],
                                   np.asarray(pr["floats"][k], np.float64))
                key = f"{role}.{k}"
                old = out.get(key, (0.0, 0.0))
                out[key] = (max(old[0], v), max(old[1], own))
    return out


def readings(cell: dict, seeds, controls=(), control_seeds=None):
    """Yield one dict of compared numbers per (seed, control)."""
    from bench import compare, controls as ctl, fleet
    prog = fleet.side(cell)
    d = fleet.reference(cell)
    control_seeds = seeds if control_seeds is None else control_seeds
    for seed in sorted(set(seeds) | set(control_seeds)):
        deals = fleet.Deals(cell, d["max_window"], seed)
        s_seeds, traces = deals.seeds, next(deals)
        t0 = time.perf_counter()
        ref_ans = fleet.run_reference(d, traces)
        ref_s = time.perf_counter() - t0
        migrations = [a["report"].get("fleet.migrations") for a in ref_ans]
        todo = ([None] if seed in seeds else []) \
            + (list(controls) if seed in control_seeds else [])
        for name in todo:
            t0 = time.perf_counter()
            try:
                if name is None:
                    runs = fleet.run_program(prog, s_seeds, traces)
                else:
                    with ctl.CONTROLS[name]():
                        runs = fleet.run_program(prog, s_seeds, traces)
                got = fleet.call_answers(runs)
                numbers = compare.compare(got, ref_ans)
                error = None
                meters = worst_meters(got, ref_ans)
            except Exception as e:     # a control that crashes has failed
                numbers, error = None, f"{type(e).__name__}: {e}"
                meters = None
            yield dict(seed=seed, control=name, numbers=numbers,
                       meters=meters, migrations=migrations,
                       correct=bool(numbers) and compare.verdict(numbers),
                       error=error, call_s=time.perf_counter() - t0,
                       reference_s=ref_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default=None)
    args = ap.parse_args(argv)
    from bench import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    cell = run.cell_with_metrics(args.workload)
    run.devices(cell["chips"])
    run.CACHE_DIR.mkdir(parents=True, exist_ok=True)
    from repro.models.compat import enable_compile_cache
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = None if args.control_seeds is None else \
        [int(s) for s in args.control_seeds.split(",")]
    controls = [c for c in args.controls.split(",") if c]
    for r in readings(cell, seeds, controls, cseeds):
        print(json.dumps(dict(workload=args.workload, **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
