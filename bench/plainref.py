"""The plain reference: what a fleet run has to answer, worked out again one
pool instance at a time.

It imports nothing of the program and shares none of its code.  Its inputs
are the benchmark's own files and the triples the program was given:

  * the configuration (`bench/configs/<config>.json`): the model, chip and
    power numbers of its `build` entries, and `profile` (the constants that
    turn them into a decode roofline and a KV and state capacity).  The
    model entry may state recurrent state beside attention:
    `attn_layer_fraction` (share of `n_layers` that hold a KV cache,
    default 1), `n_state_layers` (layers that carry recurrent state,
    default 0) and `state_bytes_per_layer` (one such layer's state for one
    sequence, whole layer before the TP split, default 0);
  * the cell's stated sizing (`bench/sizing/<cell>.json`): every pool's
    role, name, window, instance count, admission bound and overflow
    destination, in the order the pools drain;
  * the traffic's length pool (`bench.sampler`), whose mean output is the
    length the router predicts.

What a run does, as this module computes it:

  routing    requests in arrival order; a request goes to the first pool
             whose admission bound covers prompt + predicted output, and
             there to the instance with the least work assigned so far
             (prompt + predicted output, summed; first such instance);
  one instance, step by step, on its own clock:
    idle     with nothing in flight, the clock jumps to the next queued
             request's ready time, charged at idle power;
    admit    queued requests whose ready time has come take the lowest free
             slots, in queue order (queues sorted by ready time);
    decode   every slot whose prompt is done emits a token: the step lasts
             tau = (W + (S + H0 * (mean context / L)) * n) ms at n such
             slots, S the read and write of one sequence's state, and
             draws the logistic power P(n); a slot finishes when it has its
             output, and at the window's ceiling it finishes too, or, in a
             pool that overflows, is evicted to the next pool (its decode
             tokens taken back off the meters);
    prefill  512 prompt tokens a step (the chunk), lowest slot first,
             hidden behind the step's decode time where they fit; a prompt
             that completes gives its first token then.  A token costs
             2 * active parameters FLOPs for every binding: neither
             attention's quadratic term nor the state layers' own scan is
             charged, and multi-token-prediction heads are not modelled;
  overflow   evicted requests enter the next pool in order of eviction time,
             balanced on that pool's assigned work, and re-prefill there;
  meters     every charge also counts in the `m_*` meters where it falls in
             the measurement window [0.35 t_last, t_last] (decode steps by
             their midpoint, idle and prefill pro rata);
  report     per pool and for the fleet, as the program's report states it.

The answers have the layout of `bench.fleet.answers`.
"""
from __future__ import annotations

import json
import math
import pathlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import sampler

BENCH = pathlib.Path(__file__).resolve().parent
WARMUP_FRAC = 0.35
FLOAT_ROWS = ("joules", "idle_joules", "prefill_joules", "dispatch_joules",
              "handoff_joules", "m_joules", "m_prefill_joules",
              "m_idle_joules", "m_dispatch_joules", "m_handoff_joules",
              "sim_time_s", "slot_seconds", "m_slot_seconds")
INT_ROWS = ("tokens", "m_tokens", "prefill_tokens", "preempted",
            "n_escalated")


def load_sizing(cell_name: str) -> dict:
    path = BENCH / "sizing" / f"{cell_name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no such file: bench/sizing/{path.name}")
    with open(path) as f:
        return json.load(f)


def deployment(config: dict, sizing: dict, traffic: dict) -> dict:
    """Everything one run needs, from the benchmark's files alone.

    kappa, the KV bytes per token per GPU, is ceil(kv heads / tp) heads (at
    least one) of K and V in the `attn_layer_fraction` of the layers that
    hold a KV cache, with the paged cache's overhead.  sigma, the state
    bytes per sequence per GPU, is `n_state_layers` layers of
    `state_bytes_per_layer` divided over the TP ranks; it is one fixed
    slab a slot, so the paged overhead does not apply.  A pool of window w
    holds floor(budget / (kappa w + sigma)) slots, at least one; one slot
    where the weights fill the memory.  A decode step reads and writes every sequence's state
    once: S = 2 sigma at the KV-scan efficiency, per sequence a step."""
    kw = {e["name"]: e.get("kwargs", {}) for e in config["build"]}
    model, chip, power = kw["model"], kw["chip"], kw["power"]
    c = config["profile"]
    tp = c["tp"]
    heads = float(max(math.ceil(model["n_kv_heads"] / tp), 1))
    kappa = 2.0 * heads * model["head_dim"] * model["dtype_bytes"] \
        * model["n_layers"] * model.get("attn_layer_fraction", 1.0) \
        * c["kv_overhead"]
    sigma = model.get("n_state_layers", 0) \
        * model.get("state_bytes_per_layer", 0.0) / tp
    if kappa == 0 and sigma == 0:
        raise ValueError(
            f"model {model.get('name')!r} holds neither a KV cache nor "
            "recurrent state (attn_layer_fraction * n_layers and "
            "n_state_layers * state_bytes_per_layer are both 0): its "
            "binding would have no concurrency ceiling")
    weights_gpu = model["n_params"] * model["dtype_bytes"] / tp
    budget = chip["vram_bytes"] * (1.0 - c["vram_reserve_frac"]) \
        - weights_gpu

    def n_slots(window: float) -> int:
        if budget <= 0:
            return 1
        return max(int(math.floor(budget / (kappa * window + sigma))), 1)

    active = model.get("n_active_params") or model["n_params"]
    streamed = active if active < model["n_params"] else model["n_params"]
    bw = chip["mem_bw_Bps"]
    w_ms = streamed * model["dtype_bytes"] / tp \
        / (c["weight_stream_efficiency"] * bw) * 1e3
    h0_ms = kappa * c["l_calib"] / (c["kv_scan_efficiency"] * bw) * 1e3
    s_ms = 2.0 * sigma / (c["kv_scan_efficiency"] * bw) * 1e3
    w_ms += c["dispatch_ms"]
    pools = []
    for p in sizing["pools"]:
        bound = p["admit_up_to"]
        pools.append(dict(
            p, admit_up_to=math.inf if bound is None else float(bound),
            n_slots=n_slots(float(p["window"]))))
    lens = sampler.length_pool(traffic["sample"],
                               traffic["workload"]["kwargs"])
    return dict(
        pools=pools, max_window=max(p["window"] for p in pools),
        predicted_output=int(round(float(lens[1].mean()))),
        w_ms=w_ms, h0_ms=h0_ms, s_ms=s_ms, l_calib=float(c["l_calib"]),
        dispatch_s=c["dispatch_ms"] * 1e-3,
        p_idle=float(power["p_idle_w"]), p_nom=float(power["p_nom_w"]),
        k=float(power["k"]), x0=float(power["x0"]),
        chunk=config["prefill_chunk"], prefill_flops_per_token=2.0 * streamed,
        prefill_flops_per_s=tp * chip["peak_bf16_flops"] * c["prefill_mfu"])


def step_ms(d: dict, n: int, ctx: float) -> float:
    """A decode step's length at n decoding slots of mean context ctx:
    weights once, each sequence's state and KV scan once."""
    return d["w_ms"] + (d["s_ms"] + d["h0_ms"] * (ctx / d["l_calib"])) * n


class Req:
    __slots__ = ("rid", "prompt", "out", "arrival", "pool", "ready",
                 "first", "finish", "ngen", "preempt")

    def __init__(self, rid, prompt, out, arrival):
        self.rid, self.prompt, self.out, self.arrival = \
            rid, prompt, out, arrival
        self.pool = ""
        self.ready = None
        self.first = -1.0
        self.finish = -1.0
        self.ngen = 0
        self.preempt = 0

    def ready_at(self) -> float:
        return self.arrival if self.ready is None else self.ready


class Instance:
    """One instance of a pool: its queue, its slots, its clock and meters."""

    def __init__(self, pool: dict, d: dict, window: Tuple[float, float]):
        self.pool, self.d = pool, d
        self.t0, self.t1 = window
        self.queue: List[Req] = []
        self.t = 0.0
        self.m = {k: 0.0 for k in FLOAT_ROWS}
        self.n = {k: 0 for k in INT_ROWS}
        self.done: List[Req] = []
        self.evicted: List[Req] = []

    def _in(self, start: float, end: float) -> float:
        return max(0.0, min(self.t1, end) - max(self.t0, start))

    def _idle(self, dt: float) -> None:
        p = self.d["p_idle"]
        e = p * dt
        ovl = self._in(self.t, self.t + dt)
        if ovl > 0:
            self.m["m_joules"] += p * ovl
            self.m["m_idle_joules"] += p * ovl
        self.m["joules"] += e
        self.m["idle_joules"] += e
        self.t += dt

    def _prefill(self, n_tokens: int, hide: float) -> None:
        d = self.d
        span = d["prefill_flops_per_token"] * n_tokens \
            / d["prefill_flops_per_s"]
        e = d["p_nom"] * span
        hidden = min(hide, span)
        dt = span - hidden
        ovl = self._in(self.t - hidden, self.t + dt)
        if ovl > 0 and span > 0:
            self.m["m_joules"] += e * min(ovl / span, 1.0)
            self.m["m_prefill_joules"] += e * min(ovl / span, 1.0)
        self.m["joules"] += e
        self.m["prefill_joules"] += e
        self.n["prefill_tokens"] += n_tokens
        self.t += dt

    def _power(self, n: int) -> float:
        d = self.d
        return d["p_idle"] + (d["p_nom"] - d["p_idle"]) / (
            1.0 + math.exp(-d["k"] * (math.log2(n) - d["x0"])))

    def drain(self) -> None:
        d, pool = self.d, self.pool
        ceiling = pool["window"] - 1
        evicts = pool["overflow_to"] is not None
        n_slots = pool["n_slots"]
        # a slot: [request, position, tokens generated, of them in the
        # measurement window, prompt tokens left]
        slots: List[list] = [None] * n_slots
        queue = sorted(self.queue, key=Req.ready_at)
        self.queue = queue
        ready = [r.ready_at() for r in queue]
        head = 0
        while True:
            busy = [s for s in range(n_slots) if slots[s] is not None]
            if not busy and head == len(queue):
                break
            if not busy:
                dt = ready[head] - self.t
                if dt > 0:
                    self._idle(dt)
            start = self.t
            for s in range(n_slots):
                if head == len(queue) or ready[head] > self.t:
                    break
                if slots[s] is None:
                    r = queue[head]
                    head += 1
                    slots[s] = [r, r.prompt, 0, 0, r.prompt]
            occupied = sum(1 for s in slots if s is not None)
            dec = [s for s in range(n_slots)
                   if slots[s] is not None and slots[s][4] == 0]
            tau = 0.0
            if dec:
                n = len(dec)
                ctx = sum(slots[s][1] for s in dec) / n
                tau = step_ms(d, n, ctx) * 1e-3
                power = self._power(n)
                inside = self.t0 <= self.t + 0.5 * tau <= self.t1
                e = power * tau
                disp = power * min(d["dispatch_s"], tau)
                if inside:
                    self.n["m_tokens"] += n
                    self.m["m_joules"] += e
                    self.m["m_dispatch_joules"] += disp
                self.m["joules"] += e
                self.m["dispatch_joules"] += disp
                self.n["tokens"] += n
                self.t += tau
                for s in dec:
                    sl = slots[s]
                    r = sl[0]
                    sl[1] += 1
                    sl[2] += 1
                    sl[3] += inside
                    if sl[2] >= r.out or (sl[1] >= ceiling and not evicts):
                        r.ngen = sl[2]
                        r.finish = self.t
                        self.done.append(r)
                        slots[s] = None
                    elif sl[1] >= ceiling:
                        self.n["tokens"] -= max(sl[2] - 1, 0)
                        self.n["m_tokens"] -= sl[3]
                        r.preempt += 1
                        r.ready = self.t
                        self.n["preempted"] += 1
                        self.evicted.append(r)
                        slots[s] = None
            budget, hide = d["chunk"], tau
            for s in range(n_slots):
                sl = slots[s]
                if sl is None or sl[4] == 0:
                    continue
                if budget <= 0:
                    break
                take = min(budget, sl[4])
                self._prefill(take, hide)
                hide = 0.0
                sl[4] -= take
                budget -= take
                if sl[4] == 0:
                    sl[2] = 1
                    sl[0].ngen = 1
                    sl[0].first = self.t
            self.m["slot_seconds"] += occupied * (self.t - start)
            self.m["m_slot_seconds"] += occupied * self._in(start, self.t)
        self.m["sim_time_s"] = self.t


def _percentiles(arrival, first, finish, ngen) -> Dict[str, float]:
    out = {}
    if not len(arrival):
        return out
    ttft = (first - arrival)[first >= 0]
    e2e = (finish - arrival)[finish >= 0]
    ok = (finish >= 0) & (first >= 0) & (ngen > 1)
    tpot = (finish[ok] - first[ok]) / (ngen[ok] - 1)
    if len(ttft):
        out["ttft_p50_s"] = round(float(np.quantile(ttft, 0.5)), 4)
        out["ttft_p99_s"] = round(float(np.quantile(ttft, 0.99)), 4)
    if len(e2e):
        out["e2e_p99_s"] = round(float(np.quantile(e2e, 0.99)), 4)
    if len(tpot):
        out["tpot_p50_ms"] = round(float(np.quantile(tpot, 0.5)) * 1e3, 3)
        out["tpot_p99_ms"] = round(float(np.quantile(tpot, 0.99)) * 1e3, 3)
    return out


def _columns(reqs: Sequence[Req]):
    return (np.array([r.arrival for r in reqs], np.float64),
            np.array([r.first for r in reqs], np.float64),
            np.array([r.finish for r in reqs], np.float64),
            np.array([r.ngen for r in reqs], np.int64))


def run(d: dict, triples: Sequence[Tuple[int, int, float]]) -> dict:
    """One scenario: route, drain every pool in order, report."""
    reqs = [Req(i, p, o, t) for i, (p, o, t) in enumerate(triples)]
    by_arrival = sorted(reqs, key=lambda r: r.arrival)
    t_last = by_arrival[-1].arrival if reqs else 0.0
    window = (WARMUP_FRAC * t_last, t_last)
    pools = d["pools"]
    fleet = {p["role"]: [Instance(p, d, window)
                         for _ in range(p["instances"])] for p in pools}
    assigned = {p["role"]: [0.0] * p["instances"] for p in pools}
    predicted = d["predicted_output"]

    def submit(pool: dict, r: Req) -> None:
        work = assigned[pool["role"]]
        i = min(range(len(work)), key=work.__getitem__)
        work[i] += r.prompt + predicted
        r.pool = f"{pool['name']}#{i}"
        fleet[pool["role"]][i].queue.append(r)

    for r in by_arrival:
        metric = r.prompt + predicted
        submit(next(p for p in pools if metric <= p["admit_up_to"]), r)
    inbox = {p["role"]: [] for p in pools}
    migrations = 0
    for p in pools:
        for r in sorted(inbox[p["role"]], key=lambda r: r.ready):
            submit(p, r)
        for inst in fleet[p["role"]]:
            inst.drain()
            if inst.evicted:
                inbox[p["overflow_to"]].extend(inst.evicted)
                migrations += len(inst.evicted)
    return _answers(d, reqs, fleet, window, migrations)


def _answers(d, reqs, fleet, window, migrations) -> dict:
    t0, t1 = window
    span = max(t1 - t0, 1e-9)
    out_pools, report = {}, {}
    tot = dict(tok=0, joules=0.0, prefill=0.0, idle=0.0)
    done_all = []
    for p in d["pools"]:
        insts = fleet[p["role"]]
        floats = {k: np.array([x.m[k] for x in insts]) for k in FLOAT_ROWS}
        ints = {k: np.array([x.n[k] for x in insts], np.int64)
                for k in INT_ROWS}
        out_pools[p["role"]] = dict(
            shape=np.array([p["instances"], p["n_slots"], p["window"]],
                           np.int64), floats=floats, ints=ints)
        done = [r for x in insts for r in x.done]
        done_all.extend(done)
        tok, joules = int(ints["tokens"].sum()), float(floats["joules"].sum())
        avail = p["n_slots"] * float(floats["sim_time_s"].sum())
        stats = dict(
            role=p["role"], phase="decode", window=p["window"],
            instances=p["instances"], n_slots=p["n_slots"],
            completed=len(done), relayed=0,
            preempted=int(ints["preempted"].sum()), escalated=0,
            tokens=tok, joules=round(joules, 1),
            m_tokens=int(ints["m_tokens"].sum()),
            m_joules=round(float(floats["m_joules"].sum()), 1),
            m_prefill_joules=round(float(floats["m_prefill_joules"].sum()),
                                   1),
            tok_per_watt=round(tok / joules, 3) if joules else 0.0,
            occupancy=round(float(floats["slot_seconds"].sum()) / avail, 3)
            if avail else 0.0,
            sim_time_s=round(float(floats["sim_time_s"].max()), 3))
        report[p["role"]] = stats
        tot["tok"] += int(ints["m_tokens"].sum())
        tot["joules"] += float(floats["m_joules"].sum())
        tot["prefill"] += float(floats["m_prefill_joules"].sum())
        tot["idle"] += float(floats["m_idle_joules"].sum())
    for p in d["pools"]:
        # instances that went idle before the window closed draw idle power
        # up to its end
        clocks = np.array([x.t for x in fleet[p["role"]]])
        gap = float(np.maximum(0.0, t1 - np.maximum(clocks, t0)).sum())
        tot["joules"] += d["p_idle"] * gap
        tot["idle"] += d["p_idle"] * gap
    tok, joules = tot["tok"], tot["joules"]
    decode_j = joules - tot["prefill"] - tot["idle"]
    report["fleet"] = dict(
        completed=len(done_all), migrations=migrations, handoffs=0,
        escalations=0, measure_window_s=(round(t0, 3), round(t1, 3)),
        tokens=int(tok), joules=round(joules, 1),
        tokens_per_s=round(tok / span, 1),
        tok_per_watt=round(tok / joules, 3) if joules else 0.0,
        decode_tok_per_watt=round(tok / decode_j, 3) if decode_j else 0.0,
        prefill_energy_frac=round(tot["prefill"] / joules, 3)
        if joules else 0.0,
        idle_energy_frac=round(tot["idle"] / joules, 3) if joules else 0.0,
        kv_handoff_joules=0.0, kv_handoff_gb=0.0, kv_handoff_energy_frac=0.0,
        moe_dispatch_joules=round(sum(
            float(out_pools[p["role"]]["floats"]["m_dispatch_joules"].sum())
            for p in d["pools"]), 1),
        moe_dispatch_energy_frac=0.0 if not joules else round(sum(
            float(out_pools[p["role"]]["floats"]["m_dispatch_joules"].sum())
            for p in d["pools"]) / joules, 4),
        **_percentiles(*_columns(done_all)))
    flat = {}
    for part, dct in report.items():
        for k, v in dct.items():
            if isinstance(v, tuple):
                for i, x in enumerate(v):
                    flat[f"{part}.{k}.{i}"] = x
            else:
                flat[f"{part}.{k}"] = v
    return dict(
        rid=np.array([r.rid for r in reqs], np.int64),
        pool=np.array([r.pool for r in reqs]),
        req_int=np.array([[r.ngen, r.preempt, 0, int(r.finish >= 0), 0]
                          for r in reqs], np.int64),
        req_time=np.array([[r.first, r.finish,
                            np.nan if r.ready is None else r.ready]
                           for r in reqs], np.float64),
        horizon=max((r.arrival for r in reqs), default=0.0),
        order=[p["role"] for p in d["pools"]], pools=out_pools,
        report=flat)
