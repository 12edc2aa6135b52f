"""Smoke-run the program's two accelerator paths on one TPU chip.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time), phase by phase, and the script exits non-zero at the first failure:

  device  the first JAX device must be a TPU; there is no CPU fallback.
          Prints the platform, device kind and count, and the JAX, jaxlib
          and libtpu versions.
  fleet   the steady-state FleetOpt cell on Azure (Llama-3.1-70B on H100,
          b_short 4096, gamma 2, 10,000 requests, seed 0: the full config
          of benchmarks/fleet_sim_bench.py) through `prepare_spec` +
          `FleetSim.run` with the compiled engine (`engine="jax"`, the
          drain runs on the chip), then the same cell with the numpy
          oracle on the host.  The two must agree within 0.1% relative on
          completed requests, tok/W, decode tok/W and every pool's meter
          joules.
  serve   `repro.launch.serve`'s path at full width: `build_router` over
          model-mode `PoolEngine`s serving the unreduced yi-6b in bf16
          (random weights from a seed, made on the chip), then
          `ContextRouter.run`.  Every request must complete with its
          `max_new_tokens`.

Seconds printed are host wall-clock.  Trace and compile seconds are
set-up, read from JAX's own monitoring events; none of them is a device
time.  The persistent compilation cache is `JAX_COMPILATION_CACHE_DIR`
when set, else benchmarks/results/.xla_cache (`repro.models.compat`), so
a second run compiles less.

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import importlib.metadata
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.modelspec import LLAMA31_70B  # noqa: E402
from repro.core.profiles import H100_LLAMA70B  # noqa: E402
from repro.core.topospec import TopologySpec  # noqa: E402
from repro.core.workloads import AZURE  # noqa: E402
from repro.launch.serve import build_router  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.compat import enable_compile_cache  # noqa: E402
from repro.serving import Request, prepare_spec  # noqa: E402

# the repo's per-cell gate between the compiled engine and the oracle
PARITY_RTOL = 1e-3

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_SETUP_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 _BACKEND_COMPILE)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(SystemExit):
    """A phase's check failed: exit 1 with the reason on stderr."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAIL: {msg}")


class CompileLog:
    """Host seconds JAX spends tracing, lowering and compiling, from its
    monitoring events.  Traces nest, so set-up time is the union of the
    event spans.  A persistent-cache hit is a backend compile that loaded
    the executable instead of building it."""

    def __init__(self):
        self.spans = []            # (event, start, end, function name)
        self.hits = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **kw):
        if event in _SETUP_EVENTS:
            self.spans.append((event, start, end, kw.get("fun_name", "")))

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.hits += 1

    def mark(self):
        return len(self.spans), self.hits

    def since(self, mark) -> dict:
        n, hits = mark
        new = self.spans[n:]
        setup = 0.0
        end = -float("inf")
        for _, a, b, _ in sorted(new, key=lambda e: e[1]):
            setup += max(0.0, b - max(a, end))
            end = max(end, b)
        compiles = [(b - a, f) for e, a, b, f in new if e == _BACKEND_COMPILE]
        return dict(setup_s=setup,
                    compile_s=sum(d for d, _ in compiles),
                    programs=[f for _, f in compiles],
                    cache_hits=self.hits - hits)

    def close(self):
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_listener(self._event)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def fleet_phase(log: CompileLog, *, n_requests: int = 10_000,
                seed: int = 0) -> dict:
    """The FleetOpt/Azure cell through both engines; returns the deltas."""
    spec = TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                                  b_short=4096, gamma=2.0)
    runs = {}
    for engine in ("jax", "numpy"):
        sim, reqs, _ = prepare_spec(spec, AZURE, n_requests=n_requests,
                                    seed=seed, engine=engine)
        mark = log.mark()
        t0 = time.perf_counter()
        report = sim.run(reqs)
        wall = time.perf_counter() - t0
        runs[engine] = (sim, report["fleet"], wall, log.since(mark))
    (sim_j, f_j, wall_j, c_j), (sim_n, f_n, wall_n, _) = \
        runs["jax"], runs["numpy"]
    checks = {k: (f_j[k], f_n[k]) for k in
              ("completed", "tok_per_watt", "decode_tok_per_watt")}
    for role in sim_n.order:
        bj, bn = sim_j.groups[role].engine.bank, sim_n.groups[role].engine.bank
        for k in ("joules", "m_joules"):
            checks[f"{role}.{k}"] = (float(getattr(bj, k).sum()),
                                     float(getattr(bn, k).sum()))
    deltas = {k: _rel(float(a), float(b)) for k, (a, b) in checks.items()}
    worst = max(deltas, key=deltas.get)
    drains = [f for f in c_j["programs"] if "drain" in f]
    print(f"fleet: {n_requests} requests, pools {sim_n.order}; completed "
          f"jax {f_j['completed']} / numpy {f_n['completed']}; tok/W "
          f"{f_j['tok_per_watt']} / {f_n['tok_per_watt']}; decode tok/W "
          f"{f_j['decode_tok_per_watt']} / {f_n['decode_tok_per_watt']}")
    print(f"fleet: largest relative delta jax vs numpy {deltas[worst]!r} "
          f"({worst}; gate {PARITY_RTOL})")
    print(f"fleet: {len(drains)} drain signatures ({len(c_j['programs'])} "
          f"programs, {c_j['cache_hits']} persistent-cache hits)")
    print(f"fleet: host wall, jax engine {wall_j!r} s = set-up "
          f"{c_j['setup_s']!r} s (trace, lower, compile; backend compile "
          f"alone {c_j['compile_s']!r} s) + routing, pack, drain and "
          f"replay {wall_j - c_j['setup_s']!r} s; numpy engine "
          f"{wall_n!r} s")
    if f_j["completed"] != f_n["completed"]:
        raise SmokeFailure(f"completed {f_j['completed']} (jax) != "
                           f"{f_n['completed']} (numpy)")
    if deltas[worst] > PARITY_RTOL:
        raise SmokeFailure(f"{worst} differs by {deltas[worst]:.3g} "
                           f"relative (gate {PARITY_RTOL})")
    return dict(max_rel_delta=deltas[worst], drain_signatures=len(drains),
                wall_s=wall_j, **c_j)


def serve_phase(log: CompileLog, cfg, *, n_requests: int = 8,
                b_short: int = 128, window_long: int = 1024,
                prompt_lens=(96, 512), max_new: int = 32,
                seed: int = 0) -> dict:
    """`repro.launch.serve`'s router over model-mode engines; requests
    alternate between two prompt lengths, one per pool, so prefill
    compiles once per pool."""
    mark = log.mark()
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda k: M.init_params(k, cfg))(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    router = build_router(cfg, params, "fleetopt", b_short=b_short,
                          window_long=window_long, profile=H100_LLAMA70B,
                          p99_output=max_new)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, max_new_tokens=max_new,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=prompt_lens[i % 2]))
            for i in range(n_requests)]
    t1 = time.perf_counter()
    rep = router.run(reqs)
    serve_s = time.perf_counter() - t1
    c = log.since(mark)
    for name in router.pools:
        s = rep[name]
        print(f"serve: pool {name} window {s['window']} slots "
              f"{s['n_slots']}: completed {s['completed']}, tokens "
              f"{s['tokens']}, tok_per_watt {s['tok_per_watt']}")
    print(f"serve: {cfg.name} {n_params} params {cfg.dtype}; host wall "
          f"init {init_s!r} s, run {serve_s!r} s; set-up inside them "
          f"{c['setup_s']!r} s (trace, lower, compile; backend compile "
          f"alone {c['compile_s']!r} s over {len(c['programs'])} programs, "
          f"{c['cache_hits']} persistent-cache hits)")
    short = [r.rid for r in reqs if r.n_generated != max_new
             or r.finish_time is None]
    if short:
        raise SmokeFailure(f"requests {short} did not complete with "
                           f"{max_new} tokens")
    done = sum(rep[name]["completed"] for name in router.pools)
    if done != n_requests:
        raise SmokeFailure(f"{done} of {n_requests} requests completed")
    print(f"serve: all {n_requests} requests completed with {max_new} "
          "tokens each")
    return dict(init_s=init_s, serve_s=serve_s, **c)


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main() -> None:
    if os.environ.get("REPRO_FORCE_KERNEL"):
        raise SmokeFailure("REPRO_FORCE_KERNEL is set; it could put the "
                           "chip's kernels into interpret mode")
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devs)}; jax {jax.__version__}, jaxlib "
          f"{_version('jaxlib')}, libtpu {_version('libtpu')}")
    if dev.platform != "tpu":
        raise SmokeFailure(f"needs a TPU, but JAX found platform "
                           f"{dev.platform!r} ({dev.device_kind})")
    print(f"compile cache: {enable_compile_cache()}")
    log = CompileLog()
    fleet_phase(log)
    serve_phase(log, get_config("yi-6b"))
    log.close()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
