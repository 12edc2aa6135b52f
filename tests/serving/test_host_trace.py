"""The host channel of serving.telemetry: off it is one shared no-op, on it
records nested spans and counters, and a compiled FleetOpt grid records
every layer of the fleet path with its parent, its drain counters equal
what the drain returned, and its reports do not move."""
import json

import numpy as np
import pytest

from repro.core.modelspec import LLAMA31_70B
from repro.core.profiles import H100_LLAMA70B
from repro.core.topospec import TopologySpec
from repro.core.workloads import AZURE
from repro.serving import (Request, jax_engine, prepare_spec, run_fleet_grid,
                           telemetry)
from repro.serving.jax_engine import JaxPoolEngine, _bucket, drain_engines

# span -> the span it opens under in a `run_fleet_grid` call
PARENTS = {
    "fleet.prepare": None,
    "prepare.build": "fleet.prepare",
    "prepare.sim": "fleet.prepare",
    "prepare.requests": "fleet.prepare",
    "fleet.route": None,
    "grid.stage": None,
    "drain.pack": "grid.stage",
    "drain.group": "grid.stage",
    "drain.stack": "drain.group",
    "drain.launch": "drain.group",
    "drain.wait": "drain.group",
    "drain.fetch": "drain.group",
    "drain.split": "drain.group",
    "fleet.flow": "grid.stage",
    "drain.replay": "fleet.flow",
    "grid.report": None,
    "fleet.report": "grid.report",
}


def test_off_is_the_shared_noop_and_records_nothing():
    assert telemetry._host is None
    a, b = telemetry.host_span("x", k=1), telemetry.host_span("y")
    assert a is b is telemetry._OFF
    with a:
        telemetry.host_count("n", 3)
    with telemetry.host_tracing() as rec:
        pass
    assert rec.spans == [] and rec.counters == {}
    assert telemetry._host is None


def test_nesting_sets_parents_and_counters_add():
    with telemetry.host_tracing() as rec:
        with telemetry.host_span("outer", k=2):
            with telemetry.host_span("inner"):
                telemetry.host_count("n", 3)
            with telemetry.host_span("inner"):
                telemetry.host_count("n", 4)
        with telemetry.host_span("next"):
            pass
    assert telemetry._host is None
    assert [(s[0], s[3], s[4]) for s in rec.spans] == [
        ("outer", None, {"k": 2}), ("inner", 0, {}), ("inner", 0, {}),
        ("next", None, {})]
    assert rec.counters == {"n": 7}
    for name, t0, t1, parent, _ in rec.spans:
        assert t0 <= t1
        if parent is not None:
            assert rec.spans[parent][1] <= t0 and t1 <= rec.spans[parent][2]


def test_spanned_decorator_and_exceptions_close_spans():
    @telemetry.host_spanned("call")
    def f(x):
        with telemetry.host_span("fails"):
            raise ValueError(x)

    with telemetry.host_tracing() as rec:
        with pytest.raises(ValueError):
            f(1)
        with telemetry.host_span("after"):
            pass
    assert [(s[0], s[3]) for s in rec.spans] == [
        ("call", None), ("fails", 0), ("after", None)]
    assert all(s[2] is not None for s in rec.spans)
    assert f.__name__ == "f"


def _grid(monkeypatch=None, finals=None):
    """A two-stage FleetOpt grid of two small scenarios on the compiled
    engine; `finals` collects (engine, res) of every replayed drain."""
    if finals is not None:
        raw = JaxPoolEngine._finalize

        def spy(self, res, max_iters):
            finals.append((self, res))
            return raw(self, res, max_iters)
        monkeypatch.setattr(JaxPoolEngine, "_finalize", spy)
    spec = TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                                  b_short=4096)
    scenarios = [prepare_spec(spec, AZURE, n_requests=300, seed=s,
                              engine="jax") for s in (0, 1)]
    res = run_fleet_grid(scenarios)
    return [json.dumps(r.report, sort_keys=True, default=str) for r in res]


@pytest.fixture(scope="module")
def traced():
    mp = pytest.MonkeyPatch()
    finals = []
    try:
        with telemetry.host_tracing() as rec:
            reports = _grid(mp, finals)
    finally:
        mp.undo()
    return rec, reports, finals


def test_grid_records_every_layer_with_its_parent(traced):
    rec, _, _ = traced
    seen = {}
    for name, t0, t1, parent, args in rec.spans:
        assert t1 is not None and t0 <= t1, name
        seen.setdefault(name, set()).add(
            None if parent is None else rec.spans[parent][0])
    assert {n: p for n, ps in seen.items() for p in ps} == PARENTS
    assert all(len(ps) == 1 for ps in seen.values())
    stages = [s[4]["k"] for s in rec.spans if s[0] == "grid.stage"]
    assert stages == [0, 1]
    # each scenario prepares, routes and reports once; each pool replays
    count = {n: sum(s[0] == n for s in rec.spans) for n in PARENTS}
    assert count["fleet.prepare"] == count["fleet.route"] == 2
    assert count["fleet.report"] == 2
    assert count["drain.replay"] == count["drain.pack"] == 4
    assert count["drain.group"] == rec.counters["drain.groups"] == 2


def test_drain_counters_equal_what_the_drain_returned(traced):
    """`drain.iters` is the loop's own `it`, once per compiled group (each
    engine of a group is handed the same array); the entry counters are
    it x real queue entries and it x the padded (I, Q) grid."""
    rec, _, finals = traced
    groups = {}
    for eng, res in finals:
        groups.setdefault(id(res["it"]), (res["it"], []))[1].append(eng)
    assert len(groups) == rec.counters["drain.groups"]
    iters = entries = padded = 0
    shapes = []
    for it, engs in groups.values():
        it = int(it)
        rows = sum(e.instances for e in engs)
        q_pad = _bucket(max(1, int(engs[0].qlen.max())))
        shapes.append((rows, _bucket(rows), q_pad))
        iters += it
        entries += it * sum(int(e.qlen.sum()) for e in engs)
        padded += it * _bucket(rows) * q_pad
    assert rec.counters["drain.iters"] == iters > 0
    assert rec.counters["drain.entry_iters"] == entries > 0
    assert rec.counters["drain.entry_iters_padded"] == padded > entries
    # each group's span names the same rows and padded shape
    assert sorted((s[4]["rows"], s[4]["rows_padded"], s[4]["q_pad"])
                  for s in rec.spans if s[0] == "drain.group") \
        == sorted(shapes)


def test_slot_counters_measure_the_padded_slot_axis(traced):
    """`drain.slot_iters` is the loop's occupied slots summed over its
    iterations, as the drain returned it once per group, within the padded
    `drain.slot_iters_padded` (it x the padded (I, S) grid); each group's
    span names its real slots."""
    rec, _, finals = traced
    groups = {}
    for eng, res in finals:
        groups.setdefault(id(res["it"]), (res, []))[1].append(eng)
    live = padded = 0
    slots = []
    for res, engs in groups.values():
        rows = sum(e.instances for e in engs)
        live += int(res["slot_iters"])
        padded += int(res["it"]) * _bucket(rows) * _bucket(engs[0].n_slots)
        slots.append(sum(e.instances * e.n_slots for e in engs))
    assert rec.counters["drain.slot_iters"] == live > 0
    assert rec.counters["drain.slot_iters_padded"] == padded >= live
    assert sorted(s[4]["slots"] for s in rec.spans
                  if s[0] == "drain.group") == sorted(slots)


def test_admit_wave_counter_sums_what_the_drain_returned(traced):
    """`drain.admit_waves` is the admission waves the loop ran, summed
    over groups as the drain returned them.  Every group of the grid has
    min(S_pad, Q_pad) <= K, so each admitting iteration admits in one
    wave and the count stays within the group's iterations."""
    rec, _, finals = traced
    groups = {}
    for eng, res in finals:
        groups.setdefault(id(res["it"]), (res, []))[1].append(eng)
    waves = 0
    for res, engs in groups.values():
        s_pad = _bucket(engs[0].n_slots)
        q_pad = _bucket(max(1, max(int(e.qlen.max()) for e in engs)))
        assert min(s_pad, q_pad) <= jax_engine._window_len(s_pad, q_pad)
        assert 0 < int(res["admit_waves"]) <= int(res["it"])
        waves += int(res["admit_waves"])
    assert rec.counters["drain.admit_waves"] == waves


def test_admit_wave_counter_reads_zero_for_an_empty_group():
    """A group of empty pools runs no iteration and admits in no wave;
    the counter is recorded all the same."""
    eng = JaxPoolEngine(instances=3, window=4096, n_slots=4,
                        profile=H100_LLAMA70B, prefill_chunk=256,
                        streamed_params=LLAMA31_70B.streamed_params,
                        respect_arrival=True)
    eng.sort_queues()
    with telemetry.host_tracing() as rec:
        drain_engines([eng])
    assert rec.counters["drain.groups"] == 1
    assert rec.counters["drain.iters"] == 0
    assert rec.counters["drain.admit_waves"] == 0


def test_grid_reports_are_bit_identical_with_the_recorder_off(traced):
    _, reports_on, _ = traced
    assert _grid() == reports_on


@pytest.mark.parametrize("lookup", ["gather", "select"])
def test_drain_groups_name_their_lookup(lookup, monkeypatch):
    """Each `drain.group` span names the lowering of the drain's row
    lookups, and `drain.select_groups` counts the groups drained with the
    select: all of them where it is forced as on a TPU, none on the CPU's
    own gather."""
    if lookup == "select":                   # compile as for a TPU
        monkeypatch.setattr(jax_engine, "_platform", lambda: "tpu")
    engines = []
    for phase, n_slots in (("decode", 2), ("prefill", 3)):
        eng = JaxPoolEngine(instances=2, window=4096, n_slots=n_slots,
                            profile=H100_LLAMA70B, phase=phase,
                            streamed_params=LLAMA31_70B.streamed_params,
                            prefill_chunk=256, respect_arrival=True)
        for i in range(6):
            eng.submit(Request(rid=i, max_new_tokens=4, arrival_time=0.01 * i,
                               prompt=np.zeros(300, np.int64)), i % 2)
        eng.sort_queues()
        engines.append(eng)
    with telemetry.host_tracing() as rec:
        drain_engines(engines)
    groups = [s[4] for s in rec.spans if s[0] == "drain.group"]
    assert len(groups) == rec.counters["drain.groups"] == 2
    assert [g["lookup"] for g in groups] == [lookup] * 2
    assert rec.counters.get("drain.select_groups", 0) \
        == (2 if lookup == "select" else 0)
