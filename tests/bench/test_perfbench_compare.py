"""The comparison that decides `correct`, driven through a whole run of a
small FleetOpt cell on the CPU (the harness's look for a chip skipped):
the float64 drain passes, the float32 control and every planted fault of
`bench.controls` fail."""
import copy
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import compare, controls, run  # noqa: E402

CELL = "fleetopt-qwen3-235b-a22b-h100.azure-10k"
N_REQUESTS = 1000


@pytest.fixture(scope="module")
def small_cell():
    cell = run.cell_with_metrics(CELL)
    cell["traffic_data"]["n_requests"] = N_REQUESTS
    return cell


def _run(cell, seed, trace=0, seconds=0.05):
    return run.run_cell(copy.deepcopy(cell), seed=seed, seconds=seconds,
                        trace=trace, require_tpu=False,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_float64_drain_is_correct(small_cell, seed):
    out = _run(small_cell, seed)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= N_REQUESTS
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"sim_requests_per_s", "setup_s"}
    for k, c in out["checks"].items():
        assert c["value"] <= c["limit"], k


@pytest.mark.parametrize("name", sorted(controls.CONTROLS))
def test_control_and_faults_are_not_correct(small_cell, name):
    with controls.CONTROLS[name]():
        out = _run(small_cell, 7)   # a call with two overflow migrations
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_float32_control_fails_by_a_wide_margin(small_cell):
    with controls.float32():
        out = _run(small_cell, 11)
    worst = max(out["checks"][k]["value"] / out["checks"][k]["limit"]
                for k in ("time_rel", "meter_rel", "report_rel"))
    assert worst > 100


def test_traced_run_reports_host_layers(small_cell):
    out = _run(small_cell, 3, trace=1)
    assert out["correct"] is True
    for m in ("prepare_ms", "route_ms", "pack_ms", "replay_ms", "flow_ms",
              "drain_iters"):
        assert out["metrics"][m]["value"] > 0, m
    assert "breakdown" in out and list(out)[-1] == "checks"


def _answer(t=1.0, pool="a#0"):
    return dict(rid=np.arange(2), pool=np.array([pool, "b#1"]),
                req_int=np.array([[3, 0, 0, 1, 0], [5, 1, 0, 1, 0]]),
                req_time=np.array([[0.5, t, np.nan], [0.7, 2.0, 1.0]]),
                horizon=2.0, order=["a"],
                pools={"a": dict(shape=np.array([2, 4, 8]),
                                 floats={"joules": np.array([10.0, 20.0])},
                                 ints={"tokens": np.array([3, 5])})},
                report={"fleet.tok_per_watt": 1.5, "fleet.completed": 2})


def test_compare_numbers_on_known_answers():
    ref = _answer()
    assert compare.verdict(compare.compare([_answer()], [ref]))
    got = compare.compare([_answer(t=1.002)], [ref])
    assert got["time_rel"] == pytest.approx(0.001)
    assert compare.compare([_answer(pool="a#1")], [ref])[
        "pool_mismatch"] == 1
    moved = _answer()
    moved["pools"]["a"]["floats"]["joules"] = np.array([10.0, 20.002])
    assert compare.compare([moved], [ref])["meter_rel"] == \
        pytest.approx(1e-4)
    moved["report"]["fleet.completed"] = 1
    assert compare.compare([moved], [ref])["count_mismatch"] == 1
    assert not compare.verdict(compare.compare([_answer()] * 2, [ref]))
