"""The prepared reader of the drain's slot fill: the program's slot
counters as a percent, and nothing from a program without them."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.metrics import reader  # noqa: E402


def test_reads_the_slot_counters():
    ctx = dict(spans=[], counters={}, trace={}, scenarios=2,
               program_counters={"drain.slot_iters": 30_000,
                                 "drain.slot_iters_padded": 131_072})
    assert reader("drain_slot_fill")(ctx) == pytest.approx(
        100 * 30_000 / 131_072)


@pytest.mark.parametrize("counters", [
    None, {}, {"drain.slot_iters": 5},
    {"drain.slot_iters": 0, "drain.slot_iters_padded": 0}])
def test_reads_nothing_without_them(counters):
    ctx = dict(spans=[], counters={}, trace={}, scenarios=2)
    if counters is not None:
        ctx["program_counters"] = counters
    assert reader("drain_slot_fill")(ctx) is None

