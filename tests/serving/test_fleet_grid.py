"""CI smoke for the Table E grid bench (benchmarks/fleet_grid_bench.py):
a thin slice of the real grid through the exact path the full bench
takes — grid_cells composition, SHAPE_CLASSES grouping, the
run_fleet_grid stage-batched drains — cross-checked cell-for-cell
against the numpy oracle at the grid's 0.1% tok/W acceptance tolerance.

Marked `gridsmoke` (it compiles a handful of XLA drains, ~tens of
seconds on a CI core) so the plain tier-1 selection stays fast; the PR
workflow runs it as its own step.
"""
import pytest

jax = pytest.importorskip("jax")

from benchmarks import fleet_grid_bench as gb          # noqa: E402
from repro.core.topospec import TopologySpec           # noqa: E402
from repro.core.workloads import AZURE                 # noqa: E402
from repro.serving import prepare_spec, run_fleet_grid  # noqa: E402

pytestmark = pytest.mark.gridsmoke

N_REQUESTS = 120


def _slice():
    """One cheap cell per distinct drain family, H100 only."""
    cells = [c for c in gb.grid_cells()]
    picks = {}
    for label, kind, prof, mdl, kw in cells:
        if label["generation"] != "H100" or kind in picks:
            continue
        picks[kind] = (label, kind, prof, mdl, kw)
    # moe_semantic is the grid's widest family; keep the smoke to three
    # structurally distinct topologies
    return [picks[k] for k in ("fleetopt", "multipool", "moe_pool")]


def _measure(engine):
    chunk = _slice()
    scenarios = [prepare_spec(TopologySpec.from_kind(kind, prof, mdl, **kw),
                              AZURE, n_requests=N_REQUESTS, seed=0,
                              engine=engine)
                 for _, kind, prof, mdl, kw in chunk]
    floors = gb.SHAPE_CLASSES if engine == "jax" else None
    out = {}
    for (label, *_), cell in zip(
            chunk, run_fleet_grid(scenarios, pad_floors=floors)):
        out[label["topology"]] = (cell.sim_decode_tok_per_watt,
                                  cell.sim_tok_per_watt,
                                  cell.report["fleet"]["completed"])
    return out


def test_grid_slice_jax_matches_numpy_oracle():
    ref = _measure("numpy")
    got = _measure("jax")
    assert set(got) == set(ref)
    for kind, (dec, allin, done) in ref.items():
        jdec, jallin, jdone = got[kind]
        assert jdone == done, kind
        assert jdec == pytest.approx(dec, rel=1e-3), kind
        assert jallin == pytest.approx(allin, rel=1e-3), kind


def test_grid_cells_shape():
    """260 cells, every family present on every chip, labels complete."""
    cells = gb.grid_cells()
    assert len(cells) == 260
    fams = {(label["generation"], kind) for label, kind, *_ in cells}
    for gen in ("H100", "H200", "B200", "GB200"):
        for kind in ("moe_semantic", "semantic_fleetopt", "fleetopt",
                     "moe_pool", "multipool"):
            assert (gen, kind) in fams
    for label, *_ in cells:
        assert set(label) >= {"table", "generation", "workload", "topology",
                              "dispatch_ms", "misroute_rate"}
