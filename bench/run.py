"""Run one cell of BENCHMARK.json on the TPU this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Set-up: the compile cache (fixed at bench/.xla_cache inside the checkout),
the cell's configuration and traffic files, the base traces of the
benchmark's own sampler, and one warm-up call of the unit of work, which
loads or compiles every drain signature the window uses.  `setup_s` runs
from process start to the end of the warm-up; nothing of the reference
runs before it ends.

The window repeats the unit of work (`bench.fleet.run_program`: every
scenario through `serving.prepare_spec`, then one `serving.run_fleet_grid`)
back to back until `--seconds` of calls have passed, each call on a fresh
deal of the cell's traces (`bench.fleet.Deals`).  `sim_requests_per_s` is
the requests those calls simulated over the host seconds the calls took.
A program compiled or loaded from the compile cache inside the window ends
the run with exit code 3 and no result: the window would have timed it.
With `--trace 1` the window is short (at most `TRACE_SECONDS`), the
profiler and the host spans of `bench.spans` are on, and the per-layer
metrics are printed instead.

After the window the answers of one call, drawn from the seed, are compared
with the plain reference (`bench.plainref`) on the same triples
(`bench.compare`).  Each number and its limit go to stderr as the last
lines, and into the result line under `checks`.  The last line of stdout is
the result:

  {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
   "device": {...}, ["breakdown": {...},] "checks": {...}}

Exits 2, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / "bench" / ".xla_cache"
TRACE_SECONDS = 5.0

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class CompiledInWindow(SystemExit):
    """Exit 3, printing no result: a program compiled inside the window."""

    def __init__(self, programs):
        super().__init__(3)
        print(f"bench: {len(programs)} programs compiled or loaded from the "
              f"compile cache inside the measured window: {programs}",
              file=sys.stderr)


class NoDevice(SystemExit):
    """Exit 2 with the reason on stderr and no result."""

    def __init__(self, msg: str):
        super().__init__(2)
        print(f"bench: {msg}", file=sys.stderr)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"bench: device platform {d.platform}, kind {d.device_kind}, "
          f"count {len(devs)}; jax {jax.__version__}", file=sys.stderr)
    if require_tpu and d.platform != "tpu":
        raise NoDevice(f"needs a TPU, but JAX found platform {d.platform!r} "
                       f"({d.device_kind})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int | None:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _finite(x):
    return x if x == x and abs(x) != float("inf") else sys.float_info.max


def _window(prog, deals, limit: float, trace: int, rec, prof_dir):
    """Calls of the unit of work back to back, each on the next deal, until
    `limit` host seconds of calls have passed.  Only the calls are timed:
    dealing the next traces and reading a call's answers happen between
    them.  Returns (seconds of calls, [(traces, answers)] per call).  The
    seconds of each call, the process's CPU seconds in it and those of
    Python's garbage collection inside it go to stderr: a call that takes
    long on few CPU seconds waited, on the device or for a core."""
    import gc
    import jax
    from bench import fleet, spans, trace as tr
    done = []
    busy = 0.0
    each = []
    gc_s = [0.0]
    gc_t0 = [0.0]

    def gc_clock(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        else:
            gc_s[0] += time.perf_counter() - gc_t0[0]
    gc_each = []
    cpu_each = []
    with contextlib.ExitStack() as stack:
        gc.callbacks.append(gc_clock)
        stack.callback(gc.callbacks.remove, gc_clock)
        if trace:
            stack.enter_context(spans.installed(
                rec, lambda m: fleet.cells.resolve("repro", m)))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
            stack.callback(jax.profiler.stop_trace)
            stack.enter_context(jax.profiler.TraceAnnotation(tr.WINDOW_SPAN))
        while busy < limit:
            traces = next(deals)
            gc_s[0] = 0.0
            c0 = time.process_time()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(tr.CALL_SPAN):
                runs = fleet.run_program(prog, deals.seeds, traces)
            each.append(time.perf_counter() - t0)
            cpu_each.append(time.process_time() - c0)
            gc_each.append(gc_s[0])
            busy += each[-1]
            done.append((traces, fleet.call_answers(runs)))
            del runs
    print(f"bench: seconds per call {each!r}", file=sys.stderr)
    print(f"bench: process CPU seconds per call {cpu_each!r}",
          file=sys.stderr)
    print(f"bench: garbage-collection seconds per call {gc_each!r}",
          file=sys.stderr)
    return busy, done


def run_cell(cell: dict, *, seed: int, seconds: float, trace: int,
             require_tpu: bool = True, t_start: float = None,
             devs: list = None) -> dict:
    """One run of a cell (`cell_with_metrics`); returns the result object.
    Tests drive it on the CPU with `require_tpu=False`."""
    t_start = T_START if t_start is None else t_start
    devs = devs or devices(cell["chips"], require_tpu)
    import jax
    import numpy as np
    from bench import compare, fleet, spans, trace as tr

    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(jax.devices()))
    prog = fleet.side(cell)
    deals = fleet.Deals(cell, fleet.max_window(cell), seed)
    n_per_call = deals.n_requests
    log = spans.CompileLog()
    rec = spans.Spans()
    prof_dir = tempfile.mkdtemp(prefix="bench-profile-") if trace else None
    try:
        # warm-up: a call like the window's, so every drain signature is
        # compiled or loaded from the cache before the window opens
        fleet.run_program(prog, deals.seeds, next(deals))
        setup_s = time.perf_counter() - t_start
        mark = len(log.programs)
        print(f"bench: set-up compiled or loaded {log.programs!r}",
              file=sys.stderr)
        limit = min(seconds, TRACE_SECONDS) if trace else seconds
        busy_s, done = _window(prog, deals, limit, trace, rec, prof_dir)
    except Exception:       # the timed path failed: no answer to compare
        traceback.print_exc()
        if prof_dir:
            shutil.rmtree(prof_dir, ignore_errors=True)
        numbers = {k: float("inf") for k in compare.LIMITS}
        return _result(False, n_per_call, n_per_call, {}, device, numbers)
    finally:
        log.close()
    inside = log.programs[mark:]
    if inside:
        if prof_dir:
            shutil.rmtree(prof_dir, ignore_errors=True)
        raise CompiledInWindow(inside)
    device["memory_peak_bytes"] = memory_peak(devs)

    # once the window has closed and the peak is read: the reference on
    # one call drawn from the seed
    calls = len(done)
    pick = int(np.random.default_rng([seed, calls]).integers(calls))
    traces, got = done[pick]
    t0 = time.perf_counter()
    ref_ans = fleet.run_reference(fleet.reference(cell), traces)
    ref_s = time.perf_counter() - t0
    numbers = compare.compare(got, ref_ans)
    correct = compare.verdict(numbers)
    attempted = n_per_call * calls
    breakdown = None
    if trace:
        from bench.metrics import reader
        events = tr.load(prof_dir, {n for n, _, _ in rec.spans})
        shutil.rmtree(prof_dir, ignore_errors=True)
        red = tr.reduce_trace(events)
        ctx = dict(spans=rec.spans, counters=rec.counters, trace=red,
                   scenarios=calls * len(deals.seeds))
        metrics = {}
        for m in cell["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
        if red.get("busy_s"):
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = dict(device_ops=red.get("device_ops", []),
                         idle_gaps=red.get("idle_gaps", []))
    else:
        metrics = dict(
            sim_requests_per_s=dict(value=attempted / busy_s,
                                    unit="requests/s"),
            setup_s=dict(value=setup_s, unit="s"))
    migrations = [a["report"].get("fleet.migrations") for a in ref_ans]
    print(f"bench: {calls} calls of {n_per_call} requests in {busy_s!r} s "
          f"of calls; set-up {setup_s!r} s; call {pick} compared "
          f"(overflow migrations {migrations}); reference {ref_s!r} s",
          file=sys.stderr)
    return _result(correct, attempted, 0 if correct else n_per_call,
                   metrics, device, numbers, breakdown)


def _result(correct, attempted, failed, metrics, device, numbers,
            breakdown=None) -> dict:
    """The result object; prints each compared number beside its limit as
    the last lines on stderr.  `checks` comes last in the object."""
    from bench import compare
    checks = compare.checks(numbers)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out = dict(correct=correct, attempted=attempted, failed=failed,
               metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: dict(value=_finite(c["value"]), limit=c["limit"])
                     for k, c in checks.items()}
    return out


def cell_with_metrics(name: str) -> dict:
    """A cell with the manifest's metric entries that apply to it."""
    from bench import cells
    cell = cells.load_cell(name)
    man = cells.manifest()
    cell["per_layer"] = [m for m in man["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def main(argv=None) -> int:
    args = parse(argv)
    # before JAX is imported, which reads it then
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    cell = cell_with_metrics(args.workload)
    devs = devices(cell["chips"])
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    from repro.models.compat import enable_compile_cache
    enable_compile_cache()
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=args.trace, devs=devs)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0 if out["metrics"] else 1


if __name__ == "__main__":
    sys.exit(main())
