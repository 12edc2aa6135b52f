"""Every configuration, traffic and sizing file of BENCHMARK.json loads by
name, builds the program's objects and the plain reference's deployment, and
the two agree on what the files state; the plain reference answers as the
program's numpy engine does; a missing file fails loudly; every per-layer
metric has its reader."""
import importlib
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import cells, compare, fleet, plainref  # noqa: E402

MANIFEST = cells.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# every cell whose pools are stated, the cells of the manifest and those
# kept for a later one
SIZED = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(ROOT, "bench", "sizing")))


def _cell(name):
    """A cell by name, from the manifest or from its files alone."""
    if name in CELLS:
        return cells.load_cell(name)
    config, traffic = name.split(".", 1)
    return dict(name=name, config=config, traffic=traffic,
                config_data=cells.load_config(config),
                traffic_data=cells.load_traffic(traffic))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", SIZED)
def test_cell_builds_under_both_roots(name):
    """The program's objects and the plain reference's deployment, each from
    the same files, agree on the profile the drain runs with."""
    cell = _cell(name)
    prog = cells.cell_objects(cell, cells.PROGRAM_ROOT)
    d = fleet.reference(cell)
    assert type(prog["spec"]).__module__.startswith("repro.")
    assert prog["spec"].max_window == d["max_window"] == fleet.max_window(cell)
    assert round(prog["workload"].mean_output) == d["predicted_output"]
    prof = prog["spec"].pools[0].profile
    assert prof.roofline.w_ms == pytest.approx(d["w_ms"], rel=1e-12)
    assert prof.roofline.h0_ms == pytest.approx(d["h0_ms"], rel=1e-12)
    assert prof.power_model.p_idle_w == d["p_idle"]
    assert prof.tp == cell["config_data"]["profile"]["tp"]
    # the decode step itself, whatever terms either side splits it into
    roles = {p.role: p.profile for p in prog["spec"].pools}
    for p in d["pools"]:
        roofline = roles[p["role"]].roofline
        for n in sorted({1, math.ceil(p["n_slots"] / 2), p["n_slots"]}):
            for ctx in (512, p["window"] / 2, p["window"] - 1):
                assert float(roofline.tau_ms(n, ctx)) == pytest.approx(
                    plainref.step_ms(d, n, ctx), rel=1e-12), (p["role"], n,
                                                               ctx)


@pytest.mark.parametrize("name", SIZED)
def test_reference_sizes_pools_like_the_program(name):
    """The program's closed-form sizing is the one the sizing file states,
    and the slots the reference works out are the program's."""
    cell = _cell(name)
    objs = cells.cell_objects(cell, cells.PROGRAM_ROOT)
    policy, plan, _ = objs["spec"].build(objs["workload"])
    d = fleet.reference(cell)
    got = [(p.role, p.name, p.window, p.instances,
            p.profile.n_max(p.window)) for p in plan.pools]
    want = [(p["role"], p["name"], p["window"], p["instances"],
             p["n_slots"]) for p in d["pools"]]
    assert got == want and got
    assert [b for _, b in policy.ladder] == \
        [p["admit_up_to"] for p in d["pools"]]


@pytest.mark.parametrize("name", SIZED)
def test_plain_reference_answers_as_the_numpy_engine(name):
    """At a small size, the program's numpy engine and the plain reference
    give the same answers on a dealt trace with overflow migrations."""
    cell = _cell(name)
    cell["traffic_data"]["n_requests"] = 1500
    prog = fleet.side(cell)
    d = fleet.reference(cell)
    traces = next(fleet.Deals(cell, d["max_window"], 2**31 + 77))
    got = []
    for seed, tr in zip(fleet.sampler.scenario_seeds(2**31 + 77, len(traces)),
                        traces):
        sim, reqs, _ = prog.fleetsim.prepare_spec(
            prog.spec, prog.workload, n_requests=len(tr), seed=seed,
            trace=tr, engine="numpy", prefill_chunk=prog.prefill_chunk)
        got.append(fleet.answers(sim, reqs, sim.run(reqs)))
    ref = fleet.run_reference(d, traces)
    numbers = compare.compare(got, ref)
    assert compare.verdict(numbers), numbers
    assert numbers["time_rel"] < 1e-12 and numbers["meter_rel"] < 1e-12


def test_missing_files_fail_loudly():
    with pytest.raises(cells.CellError, match="no such file"):
        cells.load_config("no-such-config")
    with pytest.raises(cells.CellError, match="no such file"):
        cells.load_traffic("no-such-traffic")
    with pytest.raises(cells.CellError, match="no workload"):
        cells.load_cell("no-such.cell")
    with pytest.raises(FileNotFoundError, match="no such file"):
        plainref.load_sizing("no-such.cell")


def test_unresolvable_call_fails_loudly():
    with pytest.raises((cells.CellError, AttributeError)):
        cells.build([{"name": "x", "call": "core.no_module.Thing"}],
                    cells.PROGRAM_ROOT)


def test_manifest_names_and_files():
    for c in MANIFEST["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"] == f"bench/configs/{c['name']}.json"
    configs = {c["name"] for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["config"] in configs
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "traffic", f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "sizing", f"{w['name']}.json"))


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    mod = importlib.import_module(f"bench.metrics.{metric}")
    empty = dict(spans=[], counters={}, trace={}, scenarios=0)
    assert mod.read(empty) is None


def test_readers_on_known_spans():
    from bench.metrics import reader
    spans = [("prepare_spec", 0.0, 0.1), ("FleetSim.begin_run", 0.1, 0.15),
             ("JaxPoolEngine._pack", 0.2, 0.22),
             ("FleetSim.drain_role", 0.5, 0.6),
             ("JaxPoolEngine._finalize", 0.51, 0.55),
             ("FleetSim.finish_run", 0.6, 0.61)]
    ctx = dict(spans=spans, counters={"drain_iters": 300},
               trace=dict(busy_s=1.5, window_s=2.0, drain_device_s=1.2),
               scenarios=2)
    assert reader("prepare_ms")(ctx) == pytest.approx(50.0)
    assert reader("route_ms")(ctx) == pytest.approx(25.0)
    assert reader("pack_ms")(ctx) == pytest.approx(10.0)
    assert reader("replay_ms")(ctx) == pytest.approx(20.0)
    assert reader("flow_ms")(ctx) == pytest.approx((0.06 + 0.01) / 2 * 1e3)
    assert reader("drain_device_ms")(ctx) == pytest.approx(600.0)
    assert reader("drain_iters")(ctx) == 150
    assert reader("device_idle_share")(ctx) == pytest.approx(25.0)


def test_reference_imports_nothing_of_the_program():
    import subprocess
    code = ("import sys; import bench.plainref, bench.fleet, "
            "bench.compare; "
            "print(sorted(m for m in sys.modules "
            "if m.split('.')[0] in ('repro', 'jax')))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
