"""The plain reference's recurrent state: sigma, the state bytes of one
sequence on one GPU, takes its place in the slot count beside the KV cache
and in the decode step beside the KV scan; a binding with no state answers
exactly as before.  No program is needed."""
import copy
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells, plainref  # noqa: E402

H100 = "fleetopt-qwen3-235b-a22b-h100"
# Nemotron-3-Super-120B-A12B's hybrid block: 88 layers, of them 8 GQA
# attention layers (2 KV heads of 128) and 40 Mamba-2 layers, each holding
# an fp32 SSM state of 128 heads x 64 x 128 and a bf16 conv state of
# (8192 + 2 n_groups 128) channels x (conv 4 - 1) columns per sequence
NEMOTRON = dict(name="nemotron-3-super-shaped", n_params=120e9,
                n_active_params=12e9, n_layers=88, n_kv_heads=2,
                head_dim=128, dtype_bytes=2.0, attn_layer_fraction=8 / 88,
                n_state_layers=40,
                state_bytes_per_layer=128 * 64 * 128 * 4
                + (8192 + 2 * 8 * 128) * 3 * 2)
WINDOWS = (4096, 8192, 16384, 32768, 65536)


def _sizing():
    return dict(pools=[dict(role=f"w{w}", name=f"w{w}", window=w,
                            instances=1, admit_up_to=None, overflow_to=None)
                       for w in WINDOWS])


def _config(**model):
    config = copy.deepcopy(cells.load_config(H100))
    kw = next(e for e in config["build"] if e["name"] == "model")["kwargs"]
    kw.clear()
    kw.update(model)
    return config


def _deployment(config):
    return plainref.deployment(config, _sizing(),
                               cells.load_traffic("azure-10k"))


@pytest.mark.parametrize("cell,slots", [
    ("fleetopt-qwen3-235b-a22b-h100.azure-10k", [45, 5]),
    ("homo-qwen3-235b-a22b-h100.azure-10k", [5]),
    ("fleetopt-qwen3-235b-a22b-h100.azure-10k-x4", [45, 5])])
def test_bindings_without_state_answer_as_before(cell, slots):
    config, traffic = cell.split(".", 1)
    d = plainref.deployment(cells.load_config(config),
                            plainref.load_sizing(cell),
                            cells.load_traffic(traffic))
    assert d["s_ms"] == 0.0
    assert [p["n_slots"] for p in d["pools"]] == slots
    assert d["w_ms"] == 2.1129871876140536
    assert d["h0_ms"] == 0.16291924628099175


def test_hybrid_slots_and_state_step():
    d = _deployment(_config(**NEMOTRON))
    assert [p["n_slots"] for p in d["pools"]] == [1208, 798, 475, 262, 138]
    assert d["s_ms"] == pytest.approx(0.013124, rel=1e-4)
    assert d["w_ms"] == pytest.approx(1.1525, rel=1e-4)
    assert d["h0_ms"] == pytest.approx(0.013865, rel=1e-4)
    # without its state the same attention layers would hold
    d0 = _deployment(_config(**dict(NEMOTRON, n_state_layers=0)))
    assert [p["n_slots"] for p in d0["pools"]] == [2352, 1176, 588, 294,
                                                   147]
    assert d0["s_ms"] == 0.0


def test_attention_free_binding_is_bounded_by_its_state():
    d = _deployment(_config(**dict(NEMOTRON, attn_layer_fraction=0.0)))
    budget = 85899345920 * (1.0 - 0.035) - 120e9 * 2 / 8
    sigma = 40 * NEMOTRON["state_bytes_per_layer"] / 8
    assert [p["n_slots"] for p in d["pools"]] == \
        [math.floor(budget / sigma)] * len(WINDOWS) == [2485] * len(WINDOWS)
    assert d["h0_ms"] == 0.0 and d["s_ms"] > 0


def test_binding_with_neither_kv_nor_state_raises():
    config = _config(**dict(NEMOTRON, attn_layer_fraction=0.0,
                            n_state_layers=0))
    with pytest.raises(ValueError, match="no concurrency ceiling"):
        _deployment(config)


def test_weights_over_memory_leave_one_slot():
    d = _deployment(_config(**dict(NEMOTRON, n_params=700e9)))
    assert [p["n_slots"] for p in d["pools"]] == [1] * len(WINDOWS)


def test_first_decode_step_charges_the_state_per_sequence():
    """One instance, two requests of 100 and 200 prompt tokens and two
    output tokens each, both at t = 0: one step prefills both prompts
    (2 FLOPs a token at 2e5 FLOP/s: 1 ms and 2 ms, nothing to hide behind),
    the next decodes both at mean context 150 and ends the run."""
    w, s, h0, l_calib = 1.0, 0.5, 0.25, 100.0
    d = dict(pools=[dict(role="r", name="r", window=1024, instances=1,
                         admit_up_to=math.inf, overflow_to=None,
                         n_slots=2)],
             max_window=1024, predicted_output=2, w_ms=w, h0_ms=h0, s_ms=s,
             l_calib=l_calib, dispatch_s=0.0, p_idle=100.0, p_nom=300.0,
             k=1.0, x0=1.0, chunk=512, prefill_flops_per_token=2.0,
             prefill_flops_per_s=2e5)
    ans = plainref.run(d, [(100, 2, 0.0), (200, 2, 0.0)])
    prefill_s = 300 * 2.0 / 2e5
    tau_ms = w + (s + h0 * 150 / l_calib) * 2
    assert tau_ms == 2.75
    power = 100.0 + 200.0 / (1.0 + math.exp(-(math.log2(2) - 1.0)))
    assert power == 200.0
    f = ans["pools"]["r"]["floats"]
    assert f["sim_time_s"][0] - prefill_s == pytest.approx(2.75e-3,
                                                          rel=1e-12)
    assert f["joules"][0] - f["prefill_joules"][0] == \
        pytest.approx(200.0 * 2.75e-3, rel=1e-12)
    assert f["dispatch_joules"][0] == 0.0
    assert ans["req_time"][:, 0] == pytest.approx([1e-3, 3e-3], rel=1e-12)
    assert ans["req_time"][:, 1] == pytest.approx([f["sim_time_s"][0]] * 2,
                                                  rel=1e-12)
    assert ans["req_int"][:, 0].tolist() == [2, 2]
    assert ans["pools"]["r"]["ints"]["tokens"].tolist() == [2]
    assert plainref.step_ms(d, 2, 150.0) == tau_ms
