"""Recurrent state in the model binding: sigma, one sequence's state bytes
per GPU, takes its place in the slot ceiling beside the KV cache and, as
S, in the decode step beside the KV scan; every binding without state keeps
the integers, roofline and spec hash it had before state existed."""
import dataclasses
import math

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.hardware import B200, H100, H200, TPU_V5E
from repro.core.law import fit_one_over_w
from repro.core.modelspec import (LLAMA31_70B, PAPER_MODELS,
                                  QWEN3_235B_A22B, ModelSpec)
from repro.core.moe import moe_profile, with_dispatch_floor
from repro.core.power import H100_POWER
from repro.core.profiles import (GENERATION_PROFILES, H100_LLAMA70B,
                                 computed_profile)
from repro.core.roofline import DecodeRoofline
from repro.core.topospec import TopologySpec

# Nemotron-3-Super-120B-A12B's hybrid block: 88 layers, of them 8 GQA
# attention layers (2 KV heads of 128) and 40 Mamba-2 layers, each with an
# fp32 SSM state of 128 heads x 64 x 128 and a bf16 conv state of
# (8192 + 2 n_groups 8 x 128) channels x (conv 4 - 1) columns a sequence
NEMOTRON = ModelSpec(
    "NVIDIA-Nemotron-3-Super-120B-A12B", n_params=120e9, n_layers=88,
    n_kv_heads=2, head_dim=128, n_active_params=12e9,
    attn_layer_fraction=8 / 88, n_state_layers=40,
    state_bytes_per_layer=128 * 64 * 128 * 4 + (8192 + 2 * 8 * 128) * 3 * 2)
WINDOWS = (4096, 8192, 16384, 32768, 65536)


def _old_n_max(profile, window):
    """The slot ceiling as it was before state: tokens of KV over the
    window."""
    return max(int(math.floor(profile.kv_token_capacity / float(window))), 1)


def test_stateless_profiles_keep_their_integers():
    profiles = list(GENERATION_PROFILES.values()) + [
        computed_profile(m, chip, tp=tp) for m in PAPER_MODELS.values()
        for chip in (H100, B200, H200, TPU_V5E) for tp in (1, 2, 4, 8, 16)]
    for prof in profiles:
        assert prof.state_bytes_per_seq == 0.0 and prof.roofline.s_ms == 0.0
        for w in (1000, 2048, 4096, 8192, 16384, 32768, 65536, 131072):
            assert prof.n_max(w) == _old_n_max(prof, w), (prof.name, w)
    assert H100_LLAMA70B.n_max(8192) == 128
    qwen = moe_profile(QWEN3_235B_A22B, H100, H100_POWER, tp=8)
    assert [qwen.n_max(w) for w in (8192, 65536)] == [45, 5]


def test_hybrid_slots_and_state_step_as_the_reference():
    prof = moe_profile(NEMOTRON, H100, H100_POWER, tp=8)
    assert prof.state_bytes_per_seq == 40 * 4_255_744 / 8
    assert [prof.n_max(w) for w in WINDOWS] == [1208, 798, 475, 262, 138]
    rl = prof.roofline
    assert rl.s_ms == pytest.approx(0.013124, rel=1e-4)
    assert rl.w_ms == pytest.approx(1.1525, rel=1e-4)
    assert rl.h0_ms == pytest.approx(0.013865, rel=1e-4)
    # the same attention layers without their state
    bare = moe_profile(dataclasses.replace(NEMOTRON, n_state_layers=0), H100,
                       H100_POWER, tp=8)
    assert [bare.n_max(w) for w in WINDOWS] == [2352, 1176, 588, 294, 147]
    assert bare.roofline.s_ms == 0.0


def test_tau_charges_the_state_once_per_sequence():
    rl = DecodeRoofline(w_ms=1.0, h0_ms=0.25, l_calib=100.0, s_ms=0.5)
    assert float(rl.tau_ms(2, 150.0)) == 1.0 + (0.5 + 0.25 * 1.5) * 2
    tau = rl.tau_ms(np.array([1.0, 4.0]), np.array([100.0, 100.0]))
    np.testing.assert_array_equal(tau, [1.75, 4.0])
    # without state the step is bit for bit the stateless roofline's
    bare = dataclasses.replace(rl, s_ms=0.0)
    n, ctx = np.arange(1.0, 50.0), np.linspace(1.0, 9e4, 49)
    assert (bare.tau_ms(n, ctx) == 1.0 + bare.h_ms(ctx) * n).all()


def test_attention_free_binding_is_bounded_by_its_state():
    free = dataclasses.replace(NEMOTRON, attn_layer_fraction=0.0)
    prof = computed_profile(free, H100, H100_POWER, tp=8)
    budget = H100.vram_bytes * (1.0 - 0.035) - 120e9 * 2 / 8
    assert [prof.n_max(w) for w in WINDOWS] == \
        [math.floor(budget / (40 * 4_255_744 / 8))] * len(WINDOWS) \
        == [2485] * len(WINDOWS)
    assert prof.kv_token_capacity == math.inf


def test_binding_with_neither_kv_nor_state_has_no_ceiling():
    none = dataclasses.replace(NEMOTRON, attn_layer_fraction=0.0,
                               n_state_layers=0)
    prof = computed_profile(none, H100, H100_POWER, tp=8)
    with pytest.raises(ValueError, match="no concurrency ceiling"):
        prof.n_max(8192)


def test_weights_over_memory_leave_one_slot():
    big = dataclasses.replace(NEMOTRON, n_params=700e9)
    prof = computed_profile(big, H100, H100_POWER, tp=8)
    assert prof.weights_exceed_vram and prof.kv_token_capacity == 1.0
    assert [prof.n_max(w) for w in WINDOWS] == [1] * len(WINDOWS)


def test_dispatch_floor_keeps_the_state_term():
    prof = moe_profile(NEMOTRON, H100, H100_POWER, tp=8)
    floored = with_dispatch_floor(prof, 2.0)
    assert floored.roofline.s_ms == prof.roofline.s_ms > 0
    assert floored.roofline.w_ms == prof.roofline.w_ms + 2.0
    assert floored.roofline.h0_ms == prof.roofline.h0_ms
    assert floored.state_bytes_per_seq == prof.state_bytes_per_seq


def test_spec_hash_keys_state_only_where_held():
    """Stateless specs hash as before state existed (values of the parent
    commit); two profiles that differ only in state hash apart."""
    qwen = moe_profile(QWEN3_235B_A22B, H100, H100_POWER, tp=8)
    assert TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                                  b_short=4096).spec_hash == "73e182db6026"
    assert TopologySpec.from_kind("fleetopt", qwen, QWEN3_235B_A22B,
                                  b_short=4096, gamma=2.0).spec_hash \
        == "9dd50ca912e7"
    assert TopologySpec.from_kind("homo", qwen,
                                  QWEN3_235B_A22B).spec_hash == "8e4d9849078e"
    hybrid = moe_profile(NEMOTRON, H100, H100_POWER, tp=8)
    bare = dataclasses.replace(hybrid, state_bytes_per_seq=0.0,
                               roofline=dataclasses.replace(hybrid.roofline,
                                                            s_ms=0.0))
    assert hybrid.kv_token_capacity == bare.kv_token_capacity
    assert TopologySpec.from_kind("homo", hybrid, NEMOTRON).spec_hash \
        != TopologySpec.from_kind("homo", bare, NEMOTRON).spec_hash


def test_bridge_counts_every_state_layer():
    """`ArchConfig.analytical_spec` hands the profile every Mamba-2 or RWKV6
    block of the model, and Mamba-2's conv state beside its SSM state."""
    zamba = get_config("zamba2-2.7b")
    spec = zamba.analytical_spec()
    assert spec.n_state_layers == 5 * 9
    # 80 heads x 64 x state 64 in fp32, (5120 + 2 x 64) x 3 in bf16
    assert spec.state_bytes_per_layer == 80 * 64 * 64 * 4 \
        + (5120 + 2 * 64) * 3 * 2
    prof = computed_profile(spec, H100, H100_POWER, tp=1)
    assert prof.n_max(2048) == 251
    bare = computed_profile(dataclasses.replace(spec, n_state_layers=0),
                            H100, H100_POWER, tp=1)
    assert bare.n_max(2048) == 311
    contexts = (2048, 4096, 8192, 16384, 32768)
    # the state flattens the law: a constant slab beside a growing cache
    assert fit_one_over_w(prof, contexts=contexts).slope == pytest.approx(
        -0.7825, abs=5e-4)
    assert fit_one_over_w(bare, contexts=contexts).slope == pytest.approx(
        -0.9054, abs=5e-4)
    rwkv = get_config("rwkv6-1.6b").analytical_spec()
    assert rwkv.n_state_layers == 24
    assert rwkv.state_bytes_per_layer == 32 * 64 * 64 * 4
    ceiling = computed_profile(rwkv, H100, H100_POWER, tp=1)
    assert 1 < ceiling.n_max(2048) == ceiling.n_max(131072) < 10_000
