"""TopologySpec IR: construction validation, bit-exact provision parity
with the legacy per-kind provisioners, role round-trips, registry
binding, and spec-hash stability.

Parity is asserted with `==` (not approx): `from_kind` is pinned to the
exact float op-order of the legacy classes, so every `math.ceil`
instance count is guaranteed to land identically and the committed
quick-bench baseline can never move.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.disagg import Disaggregated
from repro.core.fleet import FleetReport, PoolSizing
from repro.core.modelspec import LLAMA31_70B, QWEN3_235B_A22B
from repro.core.multipool import MultiPool, ladder_windows
from repro.core.profiles import H100_LLAMA70B
from repro.core.routing import (LONG_WINDOW, FleetOpt, Homogeneous,
                                Semantic, TwoPool)
from repro.core.topospec import (SEMANTIC_KINDS, PoolSpec, TopologySpec,
                                 plan_roles)
from repro.core.workloads import AGENT, AZURE, LMSYS

PROF = H100_LLAMA70B
MODEL = LLAMA31_70B
WORKLOADS = (AZURE, LMSYS, AGENT)


def _legacy_twin(kind, **kw):
    """The analytical provisioner each kind's `TopologySpec.from_kind`
    compilation must reproduce (its serving-twin conventions:
    fleetopt/disagg route and serve at W = int(gamma * b_short))."""
    b_short = kw.get("b_short", 4096)
    gamma = kw.get("gamma", 2.0)
    dispatch_ms = kw.get("dispatch_ms", 0.0)
    if kind == "homo":
        return Homogeneous(), PROF, MODEL
    if kind == "moe_pool":
        # reuse the spec's floored profile object: with_dispatch_floor
        # constructs a fresh (value-equal) profile on every call
        return Homogeneous(), kw["spec"].pool("moe").profile, \
            QWEN3_235B_A22B
    if kind == "two_pool":
        return TwoPool(b_short=b_short), PROF, MODEL
    if kind == "fleetopt":
        return FleetOpt(int(gamma * b_short), gamma=1.0), PROF, MODEL
    if kind == "multipool":
        return MultiPool(kw["windows"], gamma=gamma), PROF, MODEL
    if kind in SEMANTIC_KINDS:
        g = 1.0 if kind == "semantic" else gamma
        model = QWEN3_235B_A22B if kind == "moe_semantic" else MODEL
        prof = kw["spec"].pool("large").profile \
            if kind == "moe_semantic" else PROF
        spec = kw["spec"]  # reuse the spec's derived small profile/model
        return Semantic(b_short=b_short,
                        small_profile=spec.pool("small").profile,
                        small_model=spec.models["small"], gamma=g,
                        misroute_rate=kw.get("misroute_rate", 0.0)), \
            prof, model
    if kind in ("disagg", "disagg_fleetopt"):
        return Disaggregated(b_short=int(gamma * b_short), gamma=1.0,
                             split=(kind == "disagg_fleetopt")), PROF, MODEL
    raise AssertionError(kind)


_SIZED_FIELDS = ("name", "window", "arrival_rate", "mean_output",
                 "mean_context", "mean_prompt", "hol_inflation", "phase",
                 "instances", "n_active", "power_w_per_instance",
                 "tokens_per_s", "decode_bound", "prefill_bound",
                 "n_inflight", "sized_prefill_mfu")


def _assert_reports_identical(got: FleetReport, want: FleetReport):
    assert got.label == want.label
    assert len(got.pools) == len(want.pools)
    for g, w in zip(got.pools, want.pools):
        for f in _SIZED_FIELDS:
            assert getattr(g, f) == getattr(w, f), \
                (g.name, f, getattr(g, f), getattr(w, f))
        assert g.profile is w.profile, (g.name, g.profile, w.profile)


_KIND_CASES = [
    ("homo", {}),
    ("moe_pool", {"dispatch_ms": 2.0}),
    ("two_pool", {"b_short": 4096}),
    ("fleetopt", {"b_short": 4096, "gamma": 2.0}),
    ("fleetopt", {"b_short": 1536, "gamma": 3.0}),
    ("multipool", {"windows": tuple(ladder_windows(3)), "gamma": 2.0}),
    ("multipool", {"windows": (2048, 8192, 16384, 65536), "gamma": 1.5}),
    ("semantic", {"b_short": 4096}),
    ("semantic", {"b_short": 4096, "misroute_rate": 0.05}),
    ("semantic_fleetopt", {"b_short": 4096, "gamma": 2.0}),
    ("moe_semantic", {"b_short": 4096, "gamma": 2.0, "dispatch_ms": 2.0}),
    ("disagg", {}),
    ("disagg_fleetopt", {"b_short": 4096, "gamma": 2.0}),
]


@pytest.mark.parametrize("workload", WORKLOADS, ids=lambda w: w.name)
@pytest.mark.parametrize("kind,kw", _KIND_CASES,
                         ids=[f"{k}-{i}" for i, (k, _) in
                              enumerate(_KIND_CASES)])
def test_provision_parity_bit_exact(kind, kw, workload):
    model = QWEN3_235B_A22B if kind in ("moe_pool", "moe_semantic") else MODEL
    spec = TopologySpec.from_kind(kind, PROF, model, **kw)
    legacy, prof, lmodel = _legacy_twin(kind, spec=spec, **kw)
    want = legacy.provision(workload, prof, lmodel)
    got = spec.provision(workload)
    _assert_reports_identical(got, want)


# --- satellite 1: role round-trip vs the removed topology_roles table ----

def _legacy_topology_roles(kind, plan):
    """Inline copy of the deleted `serving.fleetsim.topology_roles` kind
    table (pre-refactor), kept as the round-trip oracle."""
    if kind == "homo":
        return ["homo"]
    if kind == "moe_pool":
        return ["moe"]
    if kind in ("two_pool", "fleetopt"):
        assert len(plan.pools) == 2
        return ["short", "long"]
    if kind in SEMANTIC_KINDS:
        return ["small", "large"]
    if kind in ("multipool", "disagg", "disagg_fleetopt"):
        return [p.name for p in sorted(plan.pools, key=lambda p: p.window)]
    raise ValueError(kind)


@pytest.mark.parametrize("kind,kw", _KIND_CASES,
                         ids=[f"{k}-{i}" for i, (k, _) in
                              enumerate(_KIND_CASES)])
def test_roles_round_trip_legacy_table(kind, kw):
    model = QWEN3_235B_A22B if kind in ("moe_pool", "moe_semantic") else MODEL
    spec = TopologySpec.from_kind(kind, PROF, model, **kw)
    plan = spec.provision(AZURE)
    assert plan_roles(plan) == _legacy_topology_roles(kind, plan)
    # and the spec's static role list covers every provisioned role
    assert set(plan_roles(plan)) <= set(spec.roles)


def test_plan_roles_rejects_unstamped_pools():
    plan = Homogeneous().provision(AZURE, PROF, MODEL)
    with pytest.raises(ValueError, match="no router role"):
        plan_roles(plan)


# --- registry binding parity ---------------------------------------------

def test_registry_homogeneous_kinds_have_no_bindings():
    for kind in ("homo", "two_pool", "fleetopt", "disagg_fleetopt"):
        kw = {"windows": tuple(ladder_windows(3))} \
            if kind == "multipool" else {}
        reg = TopologySpec.from_kind(kind, PROF, MODEL, **kw).registry()
        assert not reg.heterogeneous
        assert reg.default.model is MODEL
        assert reg.default.profile is PROF


def test_registry_semantic_bindings():
    spec = TopologySpec.from_kind("semantic", PROF, MODEL)
    reg = spec.registry()
    assert reg.heterogeneous
    assert reg.for_role("small").model is spec.models["small"]
    assert reg.for_role("small").profile is spec.pool("small").profile
    assert reg.for_role("large").model is MODEL
    assert reg.for_role("large").profile is PROF


def test_registry_moe_dispatch():
    spec = TopologySpec.from_kind("moe_pool", PROF, QWEN3_235B_A22B,
                                  dispatch_ms=2.0)
    reg = spec.registry()
    assert reg.default.dispatch_ms == 2.0
    assert reg.default.profile.roofline.w_ms == \
        PROF.roofline.w_ms + 2.0


# --- satellite 2: construction-time validation ---------------------------

def _pool(role="a", window=4096, admit=math.inf, **kw):
    return PoolSpec(role=role, window=window, profile=PROF, admit=admit,
                    **kw)


def _spec(pools, **kw):
    kw.setdefault("models", {"default": MODEL})
    return TopologySpec(kind="custom", pools=tuple(pools), **kw)


def test_validate_empty_pools():
    with pytest.raises(ValueError, match="at least one PoolSpec"):
        _spec(())


def test_validate_duplicate_roles():
    with pytest.raises(ValueError, match="duplicate pool roles"):
        _spec([_pool("a", 4096, 4096.0), _pool("a", 65536)])


def test_validate_duplicate_names():
    with pytest.raises(ValueError, match="duplicate pool names"):
        _spec([_pool("a", 4096, 4096.0, name="p"),
               _pool("b", 65536, name="p")])


def test_validate_dangling_overflow_edge():
    with pytest.raises(ValueError, match="dangling edge"):
        _spec([_pool("a", 4096, 4096.0, overflow_to="nope"),
               _pool("b", 65536)])


def test_validate_backward_edge():
    with pytest.raises(ValueError, match="points backward"):
        _spec([_pool("a", 4096, 4096.0),
               _pool("b", 65536, escalate_to="a")])


def test_validate_evict_needs_destination():
    with pytest.raises(ValueError, match="no\n?.*overflow_to destination"):
        _spec([_pool("a", 4096, 4096.0, evict_on_overflow=True),
               _pool("b", 65536)])


def test_validate_windows_strictly_ascending():
    with pytest.raises(ValueError, match="strictly ascending"):
        _spec([_pool("a", 65536, 4096.0), _pool("b", 65536)])


def test_validate_admits_strictly_ascending():
    with pytest.raises(ValueError, match="strictly ascending"):
        _spec([_pool("a", 4096, 8192.0), _pool("b", 65536, 8192.0)])


def test_validate_last_admit_infinite():
    with pytest.raises(ValueError, match="admit everything"):
        _spec([_pool("a", 4096, 2048.0), _pool("b", 65536, 65536.0)])


def test_validate_admit_beyond_window():
    with pytest.raises(ValueError, match="exceeds\n?.*serve window"):
        _spec([_pool("a", 4096, 8192.0), _pool("b", 65536)])


def test_validate_no_admitting_pool():
    with pytest.raises(ValueError, match="cannot enter the fleet"):
        _spec([_pool("a", 4096, None)])


def test_validate_unreachable_pool():
    with pytest.raises(ValueError, match="never receive traffic"):
        _spec([_pool("a", 4096, math.inf), _pool("b", 65536, None)])


def test_validate_prefill_needs_handoff():
    with pytest.raises(ValueError, match="handoff_to"):
        _spec([_pool("pf", 4096, math.inf, phase="prefill")])


def test_validate_handoff_phase_consistent():
    with pytest.raises(ValueError, match="phase-consistent"):
        _spec([_pool("a", 4096, math.inf, handoff_to="b"),
               _pool("b", 4096, None)])


def test_validate_handoff_same_window():
    with pytest.raises(ValueError, match="crosses\n?.*window slices"):
        _spec([_pool("pf", 4096, math.inf, phase="prefill",
                     handoff_to="dec"),
               _pool("dec", 8192, None)])


def test_validate_unknown_model_key():
    with pytest.raises(ValueError, match="not in\n?.*spec.models"):
        _spec([_pool("a", 4096, math.inf, model_key="missing")])


def test_validate_misroute_range_and_flip():
    with pytest.raises(ValueError, match=r"misroute_rate must be in"):
        _spec([_pool("a")], misroute_rate=1.5)
    with pytest.raises(ValueError, match="needs a flip"):
        _spec([_pool("a")], misroute_rate=0.1)


def test_validate_flip_roles_and_escalation():
    with pytest.raises(ValueError, match="flip role"):
        _spec([_pool("a", 4096, 4096.0), _pool("b", 65536)],
              flip=("nope", "b"))
    with pytest.raises(ValueError, match="must escalate_to"):
        _spec([_pool("a", 4096, 4096.0), _pool("b", 65536)],
              flip=("a", "b"))


def test_validate_hol_and_dispatch_and_window():
    with pytest.raises(ValueError, match="hol_inflation"):
        _spec([_pool("a", hol_inflation=0.5)])
    with pytest.raises(ValueError, match="dispatch_ms"):
        _spec([_pool("a", dispatch_ms=-1.0)])
    with pytest.raises(ValueError, match="positive token count"):
        _spec([_pool("a", window=0)])
    with pytest.raises(ValueError, match="unknown phase"):
        _spec([_pool("a", phase="warp")])


@pytest.mark.parametrize("kind,kw,match", [
    ("fleetopt", dict(misroute_rate=0.1), "misroute_rate only applies"),
    ("homo", dict(dispatch_ms=2.0), "dispatch_ms only applies"),
    ("multipool", {}, "needs an ascending"),
    ("multipool", dict(windows=(8192, 4096)), "strictly ascending"),
    ("multipool", dict(windows=(4096, 4100, 65536)), "collide"),
    ("multipool", dict(windows=(4096, 65536), gamma=0.5), "gamma must be"),
    ("nope", dict(b_short=4096), "nope"),
], ids=["misroute-non-semantic", "dispatch-non-moe", "multipool-no-windows",
        "multipool-descending", "multipool-collide", "multipool-gamma",
        "unknown-kind"])
def test_from_kind_legacy_errors_preserved(kind, kw, match):
    """`from_kind` is the one kind-dispatch site, so it is also where an
    unknown kind or a kind's bad arguments are refused."""
    with pytest.raises(ValueError, match=match):
        TopologySpec.from_kind(kind, PROF, MODEL, **kw)


# --- derived facts -------------------------------------------------------

def test_max_window_subsumes_legacy_long_window():
    assert TopologySpec.from_kind("homo", PROF, MODEL).max_window \
        == LONG_WINDOW
    assert TopologySpec.from_kind(
        "multipool", PROF, MODEL,
        windows=(2048, 8192, 32768)).max_window == 32768
    assert TopologySpec.from_kind(
        "fleetopt", PROF, MODEL, long_window=131072).max_window == 131072


def test_spec_hash_stable_and_sensitive():
    a = TopologySpec.from_kind("fleetopt", PROF, MODEL)
    b = TopologySpec.from_kind("fleetopt", PROF, MODEL)
    assert a.spec_hash == b.spec_hash
    assert len(a.spec_hash) == 12
    for other in (
            TopologySpec.from_kind("fleetopt", PROF, MODEL, b_short=2048),
            TopologySpec.from_kind("fleetopt", PROF, MODEL, gamma=3.0),
            TopologySpec.from_kind("two_pool", PROF, MODEL),
            TopologySpec.from_kind("semantic", PROF, MODEL),
    ):
        assert other.spec_hash != a.spec_hash, other.kind


def test_build_returns_policy_plan_registry():
    spec = TopologySpec.from_kind("fleetopt", PROF, MODEL, b_short=4096)
    policy, plan, registry = spec.build(AZURE)
    assert policy.spec is spec
    assert policy.ladder == [("short", 8192.0), ("long", math.inf)]
    assert plan_roles(plan) == ["short", "long"]
    assert not registry.heterogeneous
