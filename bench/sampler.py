"""The benchmark's own trace generator: (prompt, output, arrival) triples.

A scenario's base trace is drawn with a copy of the program's
`serving.request.sample_trace` and `core.workloads.Workload.sample_requests`
/ `_sample`, same arithmetic, from the traffic file's fixed `base_seed`.
Each call of a run then deals the base trace's output lengths out again,
in an order drawn from (`--seed`, call number); prompts and arrivals stay
where they are.  The router sees only the prompt and the predicted output,
so every deal routes alike: the queues of a call's first drain stage are
the same on every call of every seed, and the programs the warm-up compiled
are the ones the window runs.  The work swings with the deal (a drain's
loop runs until the slowest instance is done), so a window of many fresh
deals averages that swing out where one deal per run would carry it whole
into the spread of a rate.

Kept here so a change to the program's sampler cannot move the inputs.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

Triple = Tuple[int, int, float]


def length_pool(sample: dict, workload: dict) -> Tuple[np.ndarray, np.ndarray]:
    """The fixed Monte-Carlo pool of (prompt, output) lengths a workload
    draws its requests from (`Workload._sample`)."""
    rng = np.random.default_rng(sample["pool_seed"])
    n = sample["pool_size"]
    mix = workload["prompt_mix"]
    weights = np.array([w for w, _, _ in mix])
    comp = rng.choice(len(mix), size=n, p=weights / weights.sum())
    mus = np.array([m for _, m, _ in mix])[comp]
    sigmas = np.array([s for _, _, s in mix])[comp]
    p = np.exp(rng.normal(mus, sigmas))
    o = rng.lognormal(workload["output_mu"], workload["output_sigma"], n)
    max_total = workload["max_total"]
    p = np.clip(p, 1, max_total - 1)
    o = np.clip(o, 1, max_total - p)
    return p, o


def base_trace(traffic: dict, seed: int, max_total: int,
               pool: Tuple[np.ndarray, np.ndarray] = None) -> List[Triple]:
    """`sample_trace`'s triples: `n_requests` lengths drawn from the pool
    with `default_rng(seed)`, clipped to `max_total` (the topology's
    largest window), and Poisson arrivals from
    `default_rng(seed + arrival_seed_offset)`."""
    wl, sample = traffic["workload"]["kwargs"], traffic["sample"]
    prompts, outputs = pool if pool is not None else length_pool(sample, wl)
    n = traffic["n_requests"]
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, sample["pool_size"], size=n)
    lens = np.maximum(np.stack([prompts[idx], outputs[idx]], axis=1),
                      1.0).astype(np.int64)
    rng = np.random.default_rng(seed + sample["arrival_seed_offset"])
    ts = np.cumsum(rng.exponential(1.0 / wl["arrival_rate"], size=n))
    out = []
    for i, (p, o) in enumerate(lens):
        p = int(min(p, max_total - 1))
        o = int(min(o, max_total - p))
        out.append((max(p, 1), max(o, 1), float(ts[i])))
    return out


def deal(base: Sequence[Triple], rng: np.random.Generator,
         max_total: int) -> List[Triple]:
    """The base trace with its output lengths in an order drawn from `rng`.
    Outputs move only between requests whose prompt leaves room for the
    trace's longest output within `max_total`, so every dealt pair keeps to
    the clipping bound and the set of sizes is the base trace's."""
    outs = np.array([o for _, o, _ in base], np.int64)
    if not len(base):
        return []
    room = np.array([max_total - p for p, _, _ in base]) >= outs.max()
    free = np.flatnonzero(room)
    outs[free] = outs[free[rng.permutation(len(free))]]
    return [(p, int(o), t) for (p, _, t), o in zip(base, outs)]


def base_traces(traffic: dict, max_total: int) -> List[List[Triple]]:
    """One base trace per scenario of a call, from `base_seed + 1000 k`."""
    pool = length_pool(traffic["sample"], traffic["workload"]["kwargs"])
    first = traffic["sample"]["base_seed"]
    return [base_trace(traffic, first + 1000 * k, max_total, pool)
            for k in range(traffic["scenarios"])]


def deal_call(bases: Sequence[Sequence[Triple]], seed: int, call: int,
              max_total: int) -> List[List[Triple]]:
    """The traces of call number `call` of a run with `seed`: every
    scenario's base trace dealt by `default_rng([seed, call, k])`."""
    return [deal(b, np.random.default_rng([seed, call, k]), max_total)
            for k, b in enumerate(bases)]


def scenario_seeds(seed: int, n: int) -> List[int]:
    """The simulator's own seed for each scenario of a call (`prepare_spec`'s
    `seed`, which names the engines' token streams)."""
    return [seed + 1000 * k for k in range(n)]
