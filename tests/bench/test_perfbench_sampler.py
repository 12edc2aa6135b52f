"""The benchmark's trace generator (bench/sampler.py) reproduces fixed
triples, so a cell's inputs stay put whatever the program's sampler does."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import cells, sampler  # noqa: E402

# the program's `sample_trace(AZURE, 10000, seed=0, max_total=65536)`
BASE_SEED0 = [(180, 471, 0.0007075292557919215),
                (428, 115, 0.0017327326040868264),
                (3855, 447, 0.002301281261470078),
                (660, 72, 0.003196391125065241),
                (1191, 70, 0.0034029238790817466)]
# the same prompts and arrivals, the outputs dealt in the order of run seed 0
DEALT_SEED0 = [(180, 106, 0.0007075292557919215),
               (428, 109, 0.0017327326040868264),
               (3855, 333, 0.002301281261470078),
               (660, 858, 0.003196391125065241),
               (1191, 347, 0.0034029238790817466)]
DEALT_SEED0_LAST = (70, 152, 10.028666569118958)


@pytest.fixture(scope="module")
def azure():
    return cells.load_traffic("azure-10k")


def test_golden_base_trace_seed0(azure):
    tr = sampler.base_trace(azure, 0, 65536)
    assert len(tr) == 10_000
    assert tr[:5] == BASE_SEED0
    assert sum(p for p, _, _ in tr) == 16_522_139
    assert sum(o for _, o, _ in tr) == 3_209_641
    assert tr[-1] == (70, 1302, 10.028666569118958)


def test_golden_dealt_triples_seed0(azure):
    (base,) = sampler.base_traces(azure, 65536)
    (tr,) = sampler.deal_call([base], 0, 0, 65536)
    assert tr[:5] == DEALT_SEED0
    assert tr[-1] == DEALT_SEED0_LAST


@pytest.mark.parametrize("seed", [1, 2**31 + 11, 2**40 + 3])
def test_every_deal_has_the_same_sizes_and_horizon(azure, seed):
    (base,) = sampler.base_traces(azure, 65536)
    calls = [sampler.deal_call([base], seed, j, 65536)[0] for j in range(3)]
    for tr in calls:
        assert sorted(o for _, o, _ in tr) == sorted(o for _, o, _ in base)
        # prompts and arrivals stay where they are, so every deal routes
        # alike; every pair keeps to the clipping bound
        assert [(p, t) for p, _, t in tr] == [(p, t) for p, _, t in base]
        assert all(p + o <= 65536 for p, o, _ in tr)
    assert calls[0] != calls[1] != calls[2] != base


def test_same_seed_and_call_same_triples(azure):
    (base,) = sampler.base_traces(azure, 65536)
    assert sampler.deal_call([base], 123456789, 4, 65536) == \
        sampler.deal_call([base], 123456789, 4, 65536)


@pytest.mark.parametrize("max_total", [8192, 65536])
def test_triples_are_clipped(azure, max_total):
    tr = sampler.base_trace(azure, 5, max_total)
    assert all(1 <= p <= max_total - 1 and 1 <= o and p + o <= max_total
               for p, o, _ in tr)
    dealt = sampler.deal([tuple(x) for x in tr],
                         sampler.np.random.default_rng(3), max_total)
    assert all(p + o <= max_total for p, o, _ in dealt)
    assert sorted(o for _, o, _ in dealt) == sorted(o for _, o, _ in tr)


def test_scenarios_have_their_own_base_traces(azure):
    x4 = cells.load_traffic("azure-10k-x4")
    bases = sampler.base_traces(x4, 65536)
    assert len(bases) == 4
    # the first scenario of the x4 mix is the single-scenario mix's trace
    assert bases[0] == sampler.base_traces(azure, 65536)[0]
    assert bases[1] != bases[0]
    dealt = sampler.deal_call(bases, 7, 0, 65536)
    assert dealt[0] == sampler.deal_call(bases[:1], 7, 0, 65536)[0]
    assert sampler.scenario_seeds(2**33, 4) == [
        2**33, 2**33 + 1000, 2**33 + 2000, 2**33 + 3000]
