"""jit/vmap'd scenario-batched twin of the SoA pool engine.

`JaxPoolEngine` extends `serving.soa.BatchedPoolEngine` (which stays the
bit-exact parity oracle against the scalar `PoolEngine`) with a drain that
runs as one compiled XLA program: the (I, S) slot arrays plus the MeterBank
rows become a `lax.while_loop` step over a pytree of arrays, and whole
*scenarios* batch as a leading vmap axis so a grid of fleet configurations
(different chips, misroute rates, dispatch floors, pool counts) drains in
one `jit(vmap(...))` call instead of hundreds of Python step loops.

Layout / padding / masking
  * Queues are frozen to (I, Q) arrays at drain start (FleetSim injects and
    sorts before a pool runs, exactly like the numpy engine's `_freeze`).
  * Ragged dims are padded to the batch max, bucketed to powers of two so
    nearby shapes reuse one executable: padded queue entries carry
    `ready = inf` and sit beyond `qlen`; padded slots are masked by
    `n_slots`; padded instances have `qlen = 0` and never wake up; padded
    scenarios are all-empty clones.  Masked lanes add exactly `+0.0` /
    `+0` to every accumulator, which float64 keeps exact.
  * Per-event Python work (finish / evict / escalate / handoff) moves to
    post-hoc reconstruction: the step logs one terminal event per queue
    entry into (I, Q) out-arrays (kind, time, first-token time, token
    count, step, slot) with `scatter(mode="drop")` masking, and
    `_finalize` replays them in (step, time, slot) order — the numpy
    engine's exact per-category append order — onto the live `Request`
    objects and the numpy `MeterBank`, so FleetSim's cross-pool flow
    (overflow / escalation / KV handoff) is byte-identical downstream.
  * Row lookups (`out[i, m] = v[i, idx[i, m]]`) lower by platform
    (`_take_rows`): a gather on the CPU, a one-hot select on the TPU.
    Admission reads the queue only through a K-entry window at each
    row's head (K = min(S, Q, 128), `_window_len`): the two K-entry
    blocks that hold it are selected over the Q/K blocks
    (`_block_pair`), each admitted slot reads its entry from them through
    `_take_rows`, and admission runs in waves of at most K entries, so no
    lookup of `admit` spans the (I, S, Q) product.  `emit` still looks
    each queue entry's slot up over S.

Parity contract: every meter expression replicates `energy.MeterBank`
operation-for-operation in float64 (`repro.models.compat.enable_x64` is
scoped to the drain so the model-mode f32 default is untouched).  The only
divergence is accumulation *order* on multi-slot chunk spills: the numpy
slow path advances the clock slot by slot, the kernel takes each slot's
charge instant in closed form from the spill's int32 token prefix (one
rounding of pf_num·Σtake/pf_den, no scan over the float clock), which is
last-ulp noise — the acceptance gate is 0.1% per tok/W cell, the observed
delta is ~1e-12 relative.  The decode-token LCG stream is elided entirely:
token *values* never feed back into any meter or event (the analytical
engines throw them away), except a prefill handoff's first token, which is
a pure function of (rid, seed) and is re-derived at reconstruction.

Not supported (use the numpy oracle): the legacy unchunked immediate-
prefill decode path (`prefill_chunk in (0, None)`), whose admission loop
advances the clock mid-admission, and model mode (cfg/params) — FleetSim
only ever builds chunked analytical pools.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.timeline import (EV_COMPLETE, EV_ESCALATE, EV_FIRST_TOKEN,
                                 EV_HANDOFF, EV_OVERFLOW)
from repro.models.compat import enable_x64

from .engine import _LCG_A, _LCG_C, _NEVER, DrainTruncatedError
from .soa import BatchedPoolEngine
from .telemetry import host_count, host_span, host_spanned

_EV_NONE, _EV_DONE, _EV_OVERFLOW, _EV_ESCALATE, _EV_HANDOFF = 0, 1, 2, 3, 4

# per-instance accumulator rows the device fills and _finalize copies back
_METER_KEYS = ("joules", "idle_joules", "prefill_joules", "dispatch_joules",
               "m_joules", "m_prefill_joules", "m_idle_joules",
               "m_dispatch_joules", "tokens", "m_tokens", "prefill_tokens")


# platforms whose drain reads a row's value at per-entry indices with a
# compare-and-select over the indexed axis; every other keeps the gather
_SELECT_PLATFORMS = ("tpu",)


def _lookup(platform: str) -> str:
    """The lowering of the drain's row lookups on `platform`."""
    return "select" if platform in _SELECT_PLATFORMS else "gather"


def _platform() -> str:
    """The platform the drain compiles for: the default backend, where
    `drain_engines` puts each group."""
    return jax.default_backend()


def _select_reduce(hot, vals, axis):
    """The value where `hot` along `axis` (one hot lane at most), reduced
    so that it only selects: `any` for bools, else a max over the value
    where hot and the dtype's least value elsewhere (a float keeps -0.0,
    inf and nan).  No hot lane reads that least value (False for bools)."""
    if vals.dtype == jnp.bool_:
        return (hot & vals).any(axis)
    fill = -jnp.inf if jnp.issubdtype(vals.dtype, jnp.floating) \
        else jnp.iinfo(vals.dtype).min
    return jnp.where(hot, vals, fill).max(axis)


def _select_rows(v, idx):
    """`take_along_axis(v, idx, axis=1)` for (I, N) `v` and (I, M) `idx`
    in [0, N), as a one-hot over N reduced by `_select_reduce`.  The
    longer of N and M is the minor axis of the (I, ., .) one-hot, so TPU
    lanes stay full when the other is a handful of slots."""
    n, m = v.shape[1], idx.shape[1]
    cols = jnp.arange(n, dtype=idx.dtype)
    if m >= n:
        return _select_reduce(idx[:, None, :] == cols[None, :, None],
                              v[:, :, None], 1)
    return _select_reduce(idx[:, :, None] == cols[None, None, :],
                          v[:, None, :], 2)


def _take_rows(v, idx, platform: str):
    """`out[i, m] = v[i, idx[i, m]]`, lowered for the platform the drain
    compiles for: a gather on the CPU, whose XLA makes an (I, N, M)
    one-hot ~8x slower than the gather at N=64, and a lane-parallel select
    on the TPU, where a gather serializes over its entries (4.5-7.6 ns an
    entry in the drain on a v5e).  Both give the same bits."""
    if _lookup(platform) == "select":
        return _select_rows(v, idx)
    return jnp.take_along_axis(v, idx, axis=1)


def _rank_rows(cum, ranks, platform: str):
    """Row-wise `searchsorted(cum[i], ranks[i])` (side left) of a
    nondecreasing (I, S) `cum`, lowered like `_take_rows`: a binary search
    on the CPU, on the TPU the count of `cum[i, s] < rank` over S, which
    gathers nothing."""
    method = "compare_all" if _lookup(platform) == "select" else "scan"
    return jax.vmap(partial(jnp.searchsorted, method=method))(cum, ranks)


def _window_len(n_slots: int, n_queue: int) -> int:
    """K, the queue entries `admit` reads at each row's head a wave:
    min(S, Q, 128), lowered to a divisor of Q (a power of two already for
    bucketed shapes) so that the queue views as (I, Q/K, K) blocks."""
    k = min(n_slots, n_queue, 128)
    while n_queue % k:
        k -= 1
    return k


def _pair_hot(start, k: int, n_blocks: int):
    """(I, 2, Q/K): blocks start // K and start // K + 1 of each row, one
    hot over the row's Q/K blocks of K entries (none past the last)."""
    blk = (start // k)[:, None] + jnp.arange(2, dtype=start.dtype)
    return blk[:, :, None] == jnp.arange(n_blocks, dtype=start.dtype)


def _block_pair(v, start, k: int):
    """(..., I, 2K): the K-entry blocks start // K and start // K + 1 of
    each row of a (..., I, Q) `v`, read with a one-hot over its Q/K blocks
    (2·I·Q lanes a column, a block's K entries lane-minor), so lane c
    holds entry start + c - start % K.  A block past Q reads the fill of
    `_select_reduce`."""
    *lead, I, Q = v.shape
    hot = _pair_hot(start, k, Q // k)[..., None]
    return _select_reduce(hot, v.reshape(*lead, I, 1, Q // k, k),
                          -2).reshape(*lead, I, 2 * k)


def _put_pair(v, start, vals, put, k: int):
    """(I, Q) `v` with the lanes of its block pair at `start` (as
    `_block_pair` reads them) set to (I, 2K) `vals` where `put`: a select
    over the pair's two blocks, with no scatter."""
    I, Q = v.shape
    hot = _pair_hot(start, k, Q // k)[..., None] \
        & put.reshape(I, 2, 1, k)                        # (I, 2, Q/K, K)
    new = _select_reduce(hot, vals.reshape(I, 2, 1, k), 1)
    return jnp.where(hot.any(1), new, v.reshape(I, Q // k, k)).reshape(I, Q)


def _bucket(n: int, floor: int = 8) -> int:
    """Round a ragged dim up to a power of two (>= floor) so stacked
    grids of nearby shapes reuse one compiled drain."""
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


# --------------------------------------------------------------------------
# the compiled drain: one scenario = one (I, S, Q) pool; vmap adds axis 0
# --------------------------------------------------------------------------

def _drain_one(p: Dict[str, "jax.Array"], *, phase: str, n_slots_pad: int,
               platform: str) -> Dict[str, "jax.Array"]:
    """One compiled drain over a row-concatenated batch of pools.

    Every piece of engine state is per-instance, so *many* pools — across
    scenarios, chips, even flag combinations — concatenate along the
    instance axis into a single (I, S) / (I, Q) problem: per-pool scalars
    (roofline/power constants, window, chunk, the evict/respect flags)
    ride in `p` as (I,) arrays, costing one broadcast per use but keeping
    the whole batch on one compiled program.  On a single-core CPU runner
    a distinct signature costs a ~2 s XLA build — an order of magnitude
    more than running the warmed program — so shape (S, Q, total I) is
    deliberately the only thing that forces a retrace, and rows pay no
    padding for their neighbors' instance counts.  `platform` is the one
    the drain compiles for, which picks the lowering of its row lookups
    (`_take_rows`)."""
    S = n_slots_pad
    take_rows = partial(_take_rows, platform=platform)
    evict = p["evict"]
    respect = p["respect"]
    I, Q = p["q_ready"].shape
    f64 = jnp.float64
    # token counts / step indices all fit comfortably in int32 (the
    # escalation sentinel _NEVER is iinfo(int32).max by construction) and
    # the drain is memory-bound on a CPU backend, so narrow integers buy a
    # near-2x on half the carried arrays
    i32 = jnp.int32
    qidx = jnp.arange(Q, dtype=i32)[None, :]
    sidx = jnp.arange(S, dtype=i32)[None, :]
    slot_ok = sidx < p["n_slots"][:, None]

    def zero_f(*shape):
        return jnp.zeros(shape, f64)

    def zero_i(*shape):
        return jnp.zeros(shape, i32)

    st0 = dict(
        sim_time=zero_f(I), qpos=zero_i(I), it=jnp.asarray(0, i32),
        slot_iters=jnp.asarray(0, i32), admit_waves=jnp.asarray(0, i32),
        active=jnp.zeros((I, S), bool),
        pos=zero_i(I, S), gen_count=zero_i(I, S), m_gen=zero_i(I, S),
        max_new=zero_i(I, S), prefill_left=zero_i(I, S),
        esc=jnp.full((I, S), _NEVER, i32), ready_ts=zero_f(I, S),
        slot_q=zero_i(I, S),
        slot_seconds=zero_f(I), m_slot_seconds=zero_f(I),
        preempted=zero_i(I), n_escalated=zero_i(I),
        out_kind=zero_i(I, Q), out_time=zero_f(I, Q),
        out_first=jnp.full((I, Q), -1.0, f64),
        out_ngen=zero_i(I, Q), out_step=zero_i(I, Q), out_slot=zero_i(I, Q),
        q_slot=zero_i(I, Q),
        **{k: (zero_i(I) if k in ("tokens", "m_tokens", "prefill_tokens")
               else zero_f(I)) for k in _METER_KEYS})

    @jax.named_scope("emit")
    def emit(st, mask, kind, time_val, ngen=None, first=None):
        """Record one terminal/drain event per masked slot into the
        queue-indexed out arrays.  Event masks/values live in slot space
        (I, S); rather than scattering them to queue columns (XLA:CPU
        lowers scatters to ~ms-scale loops), every queue entry reads the
        slot recorded in `q_slot` at its admission through `_take_rows`:
        a gather on the CPU, where an (I, S, Q) one-hot costs ~8x the
        gather at S=64, and a select over S on the TPU, where the gather
        serializes at 4.5-7.6 ns an entry and held ~89-97% of the drain's
        device time (TPU v5e).  A lane is live only while `slot_q` still
        points back at the entry (its slot has not been recycled), which
        makes the stale-mapping check one (I, Q) compare."""
        sq = st["q_slot"]

        def g(v):                      # (I,S) slot values at each entry
            if jnp.ndim(v) == 2 and jnp.shape(v)[1] == S:
                return take_rows(v, sq)
            return jnp.broadcast_to(v, (I, Q))    # one value for all slots

        hit = g(mask) & (g(st["slot_q"]) == qidx)
        if kind is not None:
            st["out_kind"] = jnp.where(hit, g(kind), st["out_kind"])
            st["out_time"] = jnp.where(hit, g(time_val), st["out_time"])
            st["out_step"] = jnp.where(hit, st["it"], st["out_step"])
            st["out_slot"] = jnp.where(hit, sq, st["out_slot"])
        if ngen is not None:
            st["out_ngen"] = jnp.where(hit, g(ngen), st["out_ngen"])
        if first is not None:
            st["out_first"] = jnp.where(hit, g(first), st["out_first"])
        return st

    def window_overlap(start, end):
        t0, t1 = p["t0"], p["t1"]
        if jnp.ndim(start) == 2:          # (I, S) spans vs (I,) windows
            t0, t1 = t0[:, None], t1[:, None]
        return jnp.maximum(0.0, jnp.minimum(t1, end)
                           - jnp.maximum(t0, start))

    def charge_prefill_span(st, take, cum_excl, overlap_s, sim):
        """Vectorized twin of the numpy engine's sequential per-slot chunk
        charges: per-slot work times via `MeterBank.charge_prefill_rows`'s
        expressions, per-slot charge instants (the numpy slow path's
        sequential `sim_time`) in closed form from the spill's int32
        exclusive token prefix `cum_excl`.  The spill is a water-fill in
        slot order: a slot s is charged only while cum_excl[s] < chunk,
        and then every earlier pending slot drained whole, so cum_excl[s]
        tokens were charged before it, and the clock advanced before it
        by their work time less the one hidden charge (only the first
        charged slot hides, and it lies before s exactly when
        cum_excl[s] > 0).  Uncharged slots' instants are never read.  No
        scan runs over the float clock: an f64 cumsum is an O(S^2)
        emulated reduce-window on the TPU.  Returns (st, sim', t_after)
        with t_after the post-charge instant per slot (first-token /
        handoff timestamps)."""
        pf_num, pf_den = p["pf_num"][:, None], p["pf_den"][:, None]
        t = (pf_num * take) / pf_den
        e = p["p_nom"][:, None] * t
        hidden = jnp.minimum(overlap_s, t)
        dt = t - hidden
        cum_dt_excl = (pf_num * cum_excl) / pf_den - jnp.where(
            cum_excl > 0, hidden.sum(1)[:, None], 0.0)
        t_before = sim[:, None] + cum_dt_excl
        ovl = window_overlap(t_before - hidden, t_before + dt)
        safe_t = jnp.where(t > 0, t, 1.0)
        e_in = jnp.where((ovl > 0) & (t > 0),
                         e * jnp.minimum(ovl / safe_t, 1.0), 0.0)
        st["m_joules"] += e_in.sum(1)
        st["m_prefill_joules"] += e_in.sum(1)
        st["joules"] += e.sum(1)
        st["prefill_joules"] += e.sum(1)
        st["prefill_tokens"] += take.sum(1, dtype=jnp.int32)
        return st, sim + dt.sum(1), t_before + dt

    # admission reads the queue through a K-entry window at each row's
    # head, in waves of at most K entries; n_admit <= min(S, Q) entries
    # always fit one wave where min(S, Q) <= K.  The int32 columns it
    # reads are stacked once, so each step of a read is one op for all.
    K = _window_len(S, Q)
    q_cols = jnp.stack([p["q_plen"], p["q_maxnew"], p["q_esc"],
                        p["q_pdone"].astype(i32)])
    lanes = jnp.arange(2 * K, dtype=i32)[None, :]

    def admit_wave(st, n_w):
        """Admit each row's next n_w (<= K, <= its free slots) queue
        entries into its n_w lowest free slots: the j-th entry from `qpos`
        lands in the j-th lowest free slot.  The window [qpos, qpos + K)
        lies in the block pair at `qpos` (`_block_pair`); each admitted
        slot reads the pair's lane qpos % K + its free rank, so a wave
        costs I·S·2K lanes a column where a lookup over the whole queue
        cost I·S·Q."""
        qpos = st["qpos"]
        free = (~st["active"]) & slot_ok
        cum_free = jnp.cumsum(free, axis=1, dtype=i32)
        free_rank = cum_free - free
        adm = free & (free_rank < n_w[:, None])
        shift = (qpos % K)[:, None]
        at = shift + jnp.clip(free_rank, 0, K - 1)
        a_plen, a_maxnew, a_esc, a_pd = jax.vmap(
            take_rows, in_axes=(0, None))(_block_pair(q_cols, qpos, K), at)
        a_ready = take_rows(_block_pair(p["q_ready"], qpos, K), at)
        # inverse mapping for `emit`: the window's j-th entry (pair lane
        # shift + j) lands in the first slot s with cum_free[s] == j+1
        j = lanes - shift
        slot_of = _rank_rows(cum_free, j + 1, platform)
        st["q_slot"] = _put_pair(
            st["q_slot"], qpos, jnp.clip(slot_of, 0, S - 1).astype(i32),
            (j >= 0) & (j < n_w[:, None]), K)
        st["active"] = st["active"] | adm
        st["pos"] = jnp.where(adm, a_plen, st["pos"])
        st["max_new"] = jnp.where(adm, a_maxnew, st["max_new"])
        st["ready_ts"] = jnp.where(adm, a_ready, st["ready_ts"])
        st["esc"] = jnp.where(adm, a_esc, st["esc"])
        st["slot_q"] = jnp.where(adm, qpos[:, None] + free_rank, st["slot_q"])
        st["gen_count"] = jnp.where(adm, jnp.where(a_pd != 0, 1, 0),
                                    st["gen_count"])
        st["prefill_left"] = jnp.where(adm, jnp.where(a_pd != 0, 0, a_plen),
                                       st["prefill_left"])
        st["m_gen"] = jnp.where(adm, 0, st["m_gen"])
        st["qpos"] = qpos + n_w
        st["admit_waves"] = st["admit_waves"] + n_w.any().astype(i32)
        return st

    @jax.named_scope("admit")
    def admit(st, sim):
        """Head-gated FIFO admission of the ready queue prefix into the
        lowest free slots (chunked mode never advances the clock here, so
        a wave vectorizes), in waves of at most K entries: a wave fills the
        lowest free slots, so the next wave's j-th lowest free slot is the
        whole admission's (K + j)-th and waves admit what one pass would.
        Where S and Q both exceed K a `while_loop` runs a wave while a row
        has entries left: none in an iteration that admits nothing, more
        than one only in a burst."""
        rem = (qidx >= st["qpos"][:, None]) & (qidx < p["qlen"][:, None])
        # respect=False degenerates to "whole queue is ready now"
        notready = rem & (p["q_ready"] > sim[:, None]) & respect[:, None]
        first_nr = jnp.argmax(notready, axis=1).astype(i32)
        prefix_end = jnp.where(notready.any(1), first_nr, p["qlen"])
        n_ready = jnp.maximum(prefix_end - st["qpos"], 0)
        n_free = ((~st["active"]) & slot_ok).sum(1, dtype=i32)
        n_admit = jnp.minimum(n_ready, n_free)
        if min(S, Q) <= K:
            return admit_wave(st, n_admit)
        keys = ("active", "pos", "max_new", "ready_ts", "esc", "slot_q",
                "gen_count", "prefill_left", "m_gen", "q_slot", "qpos",
                "admit_waves")

        def wave(c):
            sub, n_left = c
            n_w = jnp.minimum(n_left, K)
            return admit_wave(dict(sub), n_w), n_left - n_w

        sub, _ = jax.lax.while_loop(lambda c: c[1].any(), wave,
                                    ({k: st[k] for k in keys}, n_admit))
        return {**st, **sub}

    @jax.named_scope("decode_step")
    def decode_step(st, sim):
        n_occ = st["active"].sum(1, dtype=i32)
        dec = st["active"] & (st["prefill_left"] == 0)
        n_dec = dec.sum(1, dtype=i32)
        has_dec = n_dec > 0
        nf = n_dec.astype(f64)
        mean_ctx = (st["pos"] * dec).sum(1) / jnp.where(has_dec, n_dec, 1)
        tau_ms = p["w_ms"] + (p["s_ms"] + p["h0_ms"]
                              * (mean_ctx / p["l_calib"])) * nf
        tau_s = tau_ms * 1e-3
        safe_b = jnp.maximum(nf, 1e-9)
        logistic = p["p_range"] / (
            1.0 + jnp.exp(-p["k"] * (jnp.log2(safe_b) - p["x0"])))
        power = jnp.where(nf <= 0, p["p_idle"], p["p_idle"] + logistic)
        mid = sim + 0.5 * tau_s
        in_win = (p["t0"] <= mid) & (mid <= p["t1"])
        e = power * tau_s
        dj = power * jnp.minimum(p["dispatch_s"], tau_s)
        win = has_dec & in_win
        st["m_tokens"] += jnp.where(win, n_dec, 0)
        st["m_joules"] += jnp.where(win, e, 0.0)
        st["m_dispatch_joules"] += jnp.where(win, dj, 0.0)
        st["joules"] += jnp.where(has_dec, e, 0.0)
        st["dispatch_joules"] += jnp.where(has_dec, dj, 0.0)
        st["tokens"] += jnp.where(has_dec, n_dec, 0)
        sim = sim + jnp.where(has_dec, tau_s, 0.0)
        tau_full = jnp.where(has_dec, tau_s, 0.0)
        # post-decode bookkeeping + terminal events
        st["m_gen"] += (dec & win[:, None]).astype(i32)
        st["gen_count"] += dec
        st["pos"] += dec
        gc = st["gen_count"]
        done = dec & (gc >= st["max_new"])
        escalate = dec & ~done & (gc >= st["esc"])
        at_ceiling = dec & ~done & ~escalate \
            & (st["pos"] >= p["window"][:, None] - 1)
        # no-evict pools finish a request at the context ceiling instead
        done = done | (at_ceiling & ~evict[:, None])
        at_ceiling = at_ceiling & evict[:, None]
        ev = escalate | at_ceiling
        # one fused emit for all three terminal kinds: reconstruction only
        # reads ngen on DONE rows, so charging it unconditionally is free
        kind = jnp.where(done, _EV_DONE,
                         jnp.where(escalate, _EV_ESCALATE, _EV_OVERFLOW))
        st = emit(st, done | ev, kind.astype(i32), sim[:, None], ngen=gc)
        # eviction backout: decode tokens beyond the (uncharged) first are
        # clawed back so escalated/overflowed output is never double-counted
        st["tokens"] -= (jnp.maximum(gc - 1, 0) * ev).sum(1, dtype=i32)
        st["m_tokens"] -= (st["m_gen"] * ev).sum(1, dtype=i32)
        st["preempted"] += ev.sum(1, dtype=i32)
        st["n_escalated"] += escalate.sum(1, dtype=i32)
        clr = done | ev
        st["active"] = st["active"] & ~clr
        st["prefill_left"] = jnp.where(clr, 0, st["prefill_left"])
        st["gen_count"] = jnp.where(clr, 0, st["gen_count"])
        st["m_gen"] = jnp.where(clr, 0, st["m_gen"])
        st["esc"] = jnp.where(clr, _NEVER, st["esc"])
        # chunked-prefill interleave riding this row's decode tau: the
        # chunk budget spills across pending slots in slot order, only the
        # first charge hides behind the decode pass
        pend = st["active"] & (st["prefill_left"] > 0)
        pl = jnp.where(pend, st["prefill_left"], 0)
        cum_excl = jnp.cumsum(pl, axis=1) - pl
        take = jnp.minimum(pl, jnp.maximum(p["chunk"][:, None]
                                           - cum_excl, 0))
        charged = take > 0
        # a positive chunk always charges the first pending slot, whose
        # exclusive prefix is the only zero one among charged slots
        is_first = charged & (cum_excl == 0)
        ov = jnp.where(is_first, tau_full[:, None], 0.0)
        st, sim, t_after = charge_prefill_span(st, take, cum_excl, ov, sim)
        drained = charged & (take == pl)
        st = emit(st, drained, None, None, first=t_after)
        st["gen_count"] = jnp.where(drained, 1, st["gen_count"])
        st["prefill_left"] = st["prefill_left"] - take
        return st, sim, n_occ

    @jax.named_scope("coast")
    def coast(st, sim):
        """Event-free fast-forward for decode rows.  When a row's in-flight
        set is static — no slot will reach done/escalate/ceiling, no prompt
        chunks are pending, no admission can land, and every step midpoint
        stays on one side of the measurement window — the decode recurrence
        is closed-form: batch size and power are constant and the mean
        context grows by exactly one per step, so tau is linear in the step
        index and each accumulator advance is an arithmetic series.  The
        jump length is bounded conservatively (tau at the last candidate
        step upper-bounds every step), so a window/arrival/dispatch
        boundary is approached in a few geometrically-shrinking coasts and
        crossed by normal single steps.  Rows coast independently — all
        engine state is per-row, and per-row event order only needs `it`
        to grow per kernel iteration — so the jumped state matches the
        stepped oracle to accumulation-order ulps."""
        act = st["active"]
        n = act.sum(1, dtype=i32)
        has_act = n > 0
        nf = n.astype(f64)
        no_pf = ~(act & (st["prefill_left"] > 0)).any(1)
        c0 = (st["pos"] * act).sum(1) / jnp.where(has_act, n, 1)
        tau1 = (p["w_ms"] + (p["s_ms"] + p["h0_ms"] * (c0 / p["l_calib"]))
                * nf) * 1e-3
        dtau = (p["h0_ms"] / p["l_calib"]) * nf * 1e-3
        big = jnp.asarray(1 << 30, i32)
        bigf = jnp.asarray(float(1 << 30), f64)

        def floor_div(x, y):
            return jnp.floor(jnp.minimum(x / y, bigf)).astype(i32)

        # steps until the first slot event: done at max_new-gc, escalate at
        # esc-gc, ceiling at (window-1)-pos; coast strictly before the min
        rem = jnp.minimum(jnp.minimum(st["max_new"] - st["gen_count"],
                                      st["esc"] - st["gen_count"]),
                          (p["window"][:, None] - 1) - st["pos"])
        j_ev = jnp.min(jnp.where(act, rem, big), axis=1) - 1

        remq = (qidx >= st["qpos"][:, None]) & (qidx < p["qlen"][:, None])
        has_q = remq.any(1)
        free_any = ((~act) & slot_ok).any(1)
        gap_a = jnp.where(respect,                  # else "ready now"
                          jnp.min(jnp.where(remq, p["q_ready"], jnp.inf),
                                  axis=1) - sim, 0.0)
        after = sim > p["t1"]
        inwin = ~after & (sim >= p["t0"])
        gap_w = jnp.where(inwin, p["t1"] - sim, p["t0"] - sim)
        d = p["dispatch_s"]

        def bounds(t_ub):
            j_win = jnp.where(after, big, floor_div(gap_w, t_ub))
            # an arrival only binds while a free slot could accept it
            j_arr = jnp.where(has_q & free_any,
                              floor_div(jnp.maximum(gap_a, 0.0), t_ub), big)
            # min(dispatch_s, tau) must not switch branch mid-jump
            j_dis = jnp.where((d > tau1) & (dtau > 0),
                              floor_div(d - tau1, dtau) + 1, big)
            return jnp.minimum(jnp.minimum(j_win, j_arr), j_dis)

        t_ub = jnp.maximum(tau1 + jnp.maximum(j_ev - 1, 0) * dtau, 1e-12)
        j = jnp.minimum(j_ev, bounds(t_ub))
        t_ub = jnp.maximum(tau1 + jnp.maximum(j - 1, 0) * dtau, 1e-12)
        j = jnp.minimum(j_ev, bounds(t_ub))     # tightening pass
        go = has_act & no_pf & (j >= 1)
        jn = jnp.where(go, j, 0)
        jf = jn.astype(f64)
        span = jf * tau1 + dtau * (jf * (jf - 1) * 0.5)
        safe_b = jnp.maximum(nf, 1e-9)
        logistic = p["p_range"] / (
            1.0 + jnp.exp(-p["k"] * (jnp.log2(safe_b) - p["x0"])))
        power = p["p_idle"] + logistic
        e = power * span
        dj = power * jnp.where(d <= tau1, jf * d, span)
        win = go & inwin
        st["tokens"] += jnp.where(go, jn * n, 0)
        st["joules"] += jnp.where(go, e, 0.0)
        st["dispatch_joules"] += jnp.where(go, dj, 0.0)
        st["m_tokens"] += jnp.where(win, jn * n, 0)
        st["m_joules"] += jnp.where(win, e, 0.0)
        st["m_dispatch_joules"] += jnp.where(win, dj, 0.0)
        adv = jnp.where(go, span, 0.0)
        st["slot_seconds"] += nf * adv
        st["m_slot_seconds"] += nf * window_overlap(sim, sim + adv)
        coasted = act & go[:, None]
        st["gen_count"] += jnp.where(coasted, jn[:, None], 0)
        st["pos"] += jnp.where(coasted, jn[:, None], 0)
        st["m_gen"] += jnp.where(coasted & win[:, None], jn[:, None], 0)
        return st, sim + adv

    @jax.named_scope("prefill_step")
    def prefill_step(st, sim):
        """Prefill-phase lockstep: drain up to one chunk across occupied
        slots oldest-first (stable sort on ready_ts, ties to the lowest
        slot); a slot whose prompt drains emits its handoff event."""
        n_occ = st["active"].sum(1, dtype=i32)
        pend = st["active"] & (st["prefill_left"] > 0)
        key = jnp.where(pend, st["ready_ts"], jnp.inf)
        order = jnp.argsort(key, axis=1, stable=True)
        inv = jnp.argsort(order, axis=1)
        pl_srt = take_rows(jnp.where(pend, st["prefill_left"], 0), order)
        cum_excl = jnp.cumsum(pl_srt, axis=1) - pl_srt
        take_srt = jnp.minimum(pl_srt,
                               jnp.maximum(p["chunk"][:, None] - cum_excl, 0))
        st, sim, t_after_srt = charge_prefill_span(
            st, take_srt, cum_excl, jnp.zeros((I, S)), sim)
        drained_srt = (take_srt > 0) & (take_srt == pl_srt)
        take = take_rows(take_srt, inv)
        drained = take_rows(drained_srt, inv)
        t_after = take_rows(t_after_srt, inv)
        st["prefill_left"] = st["prefill_left"] - take
        st = emit(st, drained, _EV_HANDOFF, t_after, ngen=1, first=t_after)
        st["active"] = st["active"] & ~drained
        st["gen_count"] = jnp.where(drained, 0, st["gen_count"])
        st["esc"] = jnp.where(drained, _NEVER, st["esc"])
        return st, sim, n_occ

    @jax.named_scope("idle_skip")
    def idle_skip(st, sim):
        """Event-driven idle skip (respect_arrival only): rows with nothing
        in flight jump to their queue's next arrival, idle power accruing
        over the gap."""
        active_any = st["active"].any(1)
        has_q = st["qpos"] < p["qlen"]
        rem = (qidx >= st["qpos"][:, None]) & (qidx < p["qlen"][:, None])
        min_ready = jnp.min(jnp.where(rem, p["q_ready"], jnp.inf), axis=1)
        dt = min_ready - sim
        do = respect & (~active_any) & has_q & (dt > 0)
        dtc = jnp.where(do, dt, 0.0)
        e = p["p_idle"] * dtc
        ovl = window_overlap(sim, sim + dtc)
        e_in = jnp.where(do & (ovl > 0), p["p_idle"] * ovl, 0.0)
        st["m_joules"] += e_in
        st["m_idle_joules"] += e_in
        st["joules"] += jnp.where(do, e, 0.0)
        st["idle_joules"] += jnp.where(do, e, 0.0)
        return st, sim + dtc

    # each phase of the loop carries a named scope, so the device trace's
    # ops keep a stable name (".../decode_step/emit/...") across refactors
    def body(st):
        st = dict(st)
        st, sim = idle_skip(st, st["sim_time"])
        t_start = sim
        st = admit(st, sim)
        if phase == "prefill":
            st, sim, n_occ = prefill_step(st, sim)
        else:
            st, sim, n_occ = decode_step(st, sim)
        st["slot_seconds"] += n_occ * (sim - t_start)
        st["m_slot_seconds"] += n_occ * window_overlap(t_start, sim)
        # occupied slots this iteration, summed over the loop
        st["slot_iters"] = st["slot_iters"] + n_occ.sum(dtype=i32)
        if phase != "prefill":
            st, sim = coast(st, sim)
        st["sim_time"] = sim
        st["it"] = st["it"] + 1
        return st

    @jax.named_scope("cond")
    def cond(st):
        alive = st["active"].any() | (st["qpos"] < p["qlen"]).any()
        return alive & (st["it"] < p["max_iters"])

    return jax.lax.while_loop(cond, body, st0)


# one compiled program per (phase, n_slots_pad, platform) and argument shapes
_drain = jax.jit(_drain_one,
                 static_argnames=("phase", "n_slots_pad", "platform"))


# --------------------------------------------------------------------------
# host side: pack queues, batch drains, reconstruct events
# --------------------------------------------------------------------------

# row-pad fills: benign values for instance rows that exist only to pad
# the concatenated batch up to its bucketed shape (qlen=0 / n_slots=0
# keeps them permanently idle; 1.0 in the divisor constants avoids
# spurious NaNs in their — discarded — accumulator rows)
_PAD_ONES = ("w_ms", "h0_ms", "l_calib", "pf_den")


def drain_engines(engines: Sequence["JaxPoolEngine"], *,
                  max_iters: int = 100_000,
                  pad_floors: Optional[Sequence[tuple]] = None) -> None:
    """Drain many pools (typically one per grid scenario) as a handful of
    compiled calls.  Every piece of engine state is per-instance, so the
    pools *concatenate along the instance axis*: engines are grouped by
    padded (S, Q), their packed arrays stacked row-wise (per-pool scalars
    were broadcast to (I,) rows by `_pack`), and each group drains as one
    jitted program over the merged (sum-of-I, S/Q) arrays.  Results are
    staged on each engine by row span; its next `run_until_drained` call
    finalizes instead of re-simulating.  Rows never pay padding for a
    neighbor pool's instance count or flag/chip constants — only S and Q
    are padded, and the row total rounds up to a power-of-two bucket.

    `pad_floors` is an optional list of (i_floor, s_cap, q_cap) shape
    classes: each engine joins the cheapest (s_cap, q_cap) class that
    fits it (falling back to per-engine power-of-two buckets), and the
    class's merged row count pads to at least `i_floor` so calls of
    slightly different pool mixtures land on one compiled signature.  On
    a single-core CPU runner each distinct signature costs a ~2 s XLA
    build — which is why callers that sweep hundreds of cells
    (benchmarks/fleet_grid_bench.py) pin a survey-derived class list."""
    groups: Dict[tuple, List[JaxPoolEngine]] = {}
    packed = {}
    for eng in engines:
        params = eng._pack(max_iters)
        packed[id(eng)] = params
        S, Q = eng.n_slots, params["q_ready"].shape[1]
        dims = None
        if pad_floors:
            fits = [c for c in pad_floors if S <= c[1] and Q <= c[2]]
            if fits:        # cheapest by per-row footprint, then row floor
                dims = min(fits, key=lambda c: (c[1] + c[2], c[0]))
        if dims is None:
            dims = (1, _bucket(S), _bucket(Q))
        groups.setdefault((eng.phase, *dims), []).append(eng)
    platform = _platform()
    lookup = _lookup(platform)
    with enable_x64():
        for (phase, i_floor, s_pad, q_pad), engs in groups.items():
            rows = [packed[id(e)] for e in engs]
            i_tot = sum(e.instances for e in engs)
            i_pad = _bucket(max(i_tot, i_floor))
            slots = sum(e.instances * e.n_slots for e in engs)
            with host_span("drain.group", phase=phase, rows=i_tot,
                           rows_padded=i_pad, slots=slots, s_pad=s_pad,
                           q_pad=q_pad, lookup=lookup):
                with host_span("drain.stack"):
                    merged = _stack(rows, i_pad, q_pad)
                with host_span("drain.launch"):
                    out = _drain(jax.device_put(merged), phase=phase,
                                 n_slots_pad=s_pad, platform=platform)
                with host_span("drain.wait"):
                    out = jax.block_until_ready(out)
                with host_span("drain.fetch"):
                    out = {k: np.asarray(v) for k, v in out.items()}
                # iteration-weighted real and padded queue entries and
                # occupied and padded slots: the loop reads every (i_pad,
                # q_pad) entry and selects over every (i_pad, s_pad) slot
                # each step
                it = int(out["it"])
                host_count("drain.groups", 1)
                if lookup == "select":
                    host_count("drain.select_groups", 1)
                host_count("drain.iters", it)
                host_count("drain.entry_iters",
                           it * sum(int(r["qlen"].sum()) for r in rows))
                host_count("drain.entry_iters_padded", it * i_pad * q_pad)
                host_count("drain.slot_iters", int(out["slot_iters"]))
                # admission waves: at most one an iteration unless a burst
                # overran the K-entry window
                host_count("drain.admit_waves", int(out["admit_waves"]))
                host_count("drain.slot_iters_padded", it * i_pad * s_pad)
                with host_span("drain.split"):
                    _split(out, engs, rows)


def _stack(rows: List[dict], i_pad: int, q_pad: int) -> Dict[str, np.ndarray]:
    """Concatenate packed pools along the instance axis, padded to
    (i_pad,) and (i_pad, q_pad) host arrays."""
    merged = {}
    for k in rows[0]:
        parts = [r[k] for r in rows]
        if np.ndim(parts[0]) == 0:       # max_iters: shared scalar
            merged[k] = max(parts)
            continue
        if parts[0].ndim == 2:
            fill = np.inf if k == "q_ready" else (
                _NEVER if k == "q_esc" else 0)
            a = np.full((i_pad, q_pad), fill, parts[0].dtype)
        else:
            a = np.full((i_pad,),
                        1 if k in _PAD_ONES else 0, parts[0].dtype)
        off = 0
        for r in parts:
            n = r.shape[0]
            if r.ndim == 2:
                a[off:off + n, :r.shape[1]] = r
            else:
                a[off:off + n] = r
            off += n
        merged[k] = a
    return merged


def _split(out: Dict[str, np.ndarray], engs: Sequence["JaxPoolEngine"],
           rows: List[dict]) -> None:
    """Stage each engine's row span of a group's drained outputs on it."""
    off = 0
    for eng, packed in zip(engs, rows):
        I, S = eng.instances, eng.n_slots
        Q = packed["q_ready"].shape[1]
        res = {}
        for k, v in out.items():
            if v.ndim == 0:     # the group's `it` and loop counters
                res[k] = v
                continue
            s = v[off:off + I]
            if s.ndim == 2:
                s = s[:, :Q] if (k.startswith("out_")
                                 or k == "q_slot") else s[:, :S]
            res[k] = s
        eng._staged = res
        off += I


class JaxPoolEngine(BatchedPoolEngine):
    """Drop-in `BatchedPoolEngine` whose drive loop runs on XLA.

    Construction, submission, queue sorting, the outboxes and every
    aggregate the fleet simulator reads are inherited; only
    `run_until_drained` is replaced by pack -> compiled drain ->
    reconstruct.  `serving.jax_engine.drain_engines` batches the drains of
    many engines (a scenario grid) into single compiled calls and stages
    the results, which this method then just finalizes."""

    def __init__(self, **kw):
        super().__init__(**kw)
        if self.phase != "prefill" and not self.prefill_chunk:
            raise NotImplementedError(
                "the unchunked immediate-prefill decode path advances the "
                "clock mid-admission and is not vectorizable; use the "
                "numpy BatchedPoolEngine or pass a prefill_chunk")
        self._staged: Optional[Dict[str, np.ndarray]] = None

    # --- pack -----------------------------------------------------------

    @host_spanned("drain.pack")
    def _pack(self, max_iters: int) -> Dict[str, np.ndarray]:
        """Freeze queues into device-ready arrays + scalar params (the
        scenario pytree drain_engines stacks on the vmap axis)."""
        self._freeze()
        I = self.instances
        Q = max(1, int(self.qlen.max()))
        q_ready = np.full((I, Q), np.inf)
        q_plen = np.zeros((I, Q), np.int32)
        q_maxnew = np.zeros((I, Q), np.int32)
        q_esc = np.full((I, Q), _NEVER, np.int32)
        q_pdone = np.zeros((I, Q), bool)
        for i, q in enumerate(self.queues):
            for j, r in enumerate(q):
                q_ready[i, j] = self._ready(r)
                q_plen[i, j] = r.prompt_len
                q_maxnew[i, j] = r.max_new_tokens
                if r.escalate_at is not None:
                    q_esc[i, j] = r.escalate_at
                q_pdone[i, j] = r.prefill_done
        prof, pm, rl = self.profile, self.profile.power_model, \
            self.profile.roofline
        # pool-level constants broadcast to (I,) so row-concatenated pools
        # with different chips/flags share one compiled drain
        def ff(v):
            return np.full(I, v, np.float64)

        def fi(v):
            return np.full(I, v, np.int32)

        return dict(
            q_ready=q_ready, q_plen=q_plen, q_maxnew=q_maxnew, q_esc=q_esc,
            q_pdone=q_pdone, qlen=self.qlen.astype(np.int32),
            w_ms=ff(rl.w_ms), h0_ms=ff(rl.h0_ms), l_calib=ff(rl.l_calib),
            s_ms=ff(rl.s_ms),
            p_idle=ff(pm.p_idle_w), p_range=ff(pm.p_range_w),
            k=ff(pm.k), x0=ff(pm.x0), p_nom=ff(pm.p_nom_w),
            pf_num=ff(2.0 * self._streamed_params),
            pf_den=ff(prof.tp * prof.chip.peak_bf16_flops
                      * self.prefill_mfu),
            dispatch_s=ff(self.bank.dispatch_s),
            t0=ff(self.bank.measure_t0), t1=ff(self.bank.measure_t1),
            chunk=fi(self.prefill_chunk or 0),
            window=fi(self.window), n_slots=fi(self.n_slots),
            evict=np.full(I, self.evict_on_overflow, bool),
            respect=np.full(I, self.respect_arrival, bool),
            max_iters=np.int32(min(max_iters, np.iinfo(np.int32).max)))

    # --- drive ----------------------------------------------------------

    def run_until_drained(self, max_iters: int = 100_000) -> None:
        res = self._staged
        self._staged = None
        if res is None:
            drain_engines([self], max_iters=max_iters)
            res, self._staged = self._staged, None
        self._finalize(res, max_iters)

    # --- reconstruct ----------------------------------------------------

    @host_spanned("drain.replay")
    def _finalize(self, res: Dict[str, np.ndarray],
                  max_iters: int) -> None:
        alive = bool(res["active"].any()) \
            or bool((res["qpos"] < self.qlen).any())
        if alive:
            qleft = int((self.qlen - res["qpos"]).sum())
            raise DrainTruncatedError(
                self.name, max_iters,
                f"{qleft} queued, {int(res['active'].sum())} in flight")
        b = self.bank
        for k in _METER_KEYS:
            getattr(b, k)[:] = res[k]
        b.sim_time_s[:] = res["sim_time"]
        self.slot_seconds[:] = res["slot_seconds"]
        self.m_slot_seconds[:] = res["m_slot_seconds"]
        self.preempted[:] = res["preempted"]
        self.n_escalated[:] = res["n_escalated"]
        self.qpos[:] = self.qlen
        self._refresh_heads(np.arange(self.instances))
        kinds, times = res["out_kind"], res["out_time"]
        firsts, ngens = res["out_first"], res["out_ngen"]
        tr = self.trace
        for i in range(self.instances):
            n = int(self.qlen[i])
            if not n:
                continue
            # numpy append order: step, then within a step the per-slot
            # event sweeps (slot-ascending) / the FIFO handoff charges
            # (time-ascending — identical within a decode step)
            order = np.lexsort((res["out_slot"][i, :n], times[i, :n],
                                res["out_step"][i, :n]))
            q = self.queues[i]
            for j in order:
                j = int(j)
                kind = int(kinds[i, j])
                assert kind != _EV_NONE, (self.name, i, j)
                req = q[j]
                t = float(times[i, j])
                if firsts[i, j] >= 0:
                    # the request's prompt drained here (chunk interleave):
                    # first token emitted at that instant
                    req.first_token_time = float(firsts[i, j])
                    req.n_generated = 1
                    if tr is not None:
                        tr.event(EV_FIRST_TOKEN, req.rid, self._trace_pool,
                                 i, req.first_token_time)
                if kind == _EV_DONE:
                    req.n_generated = int(ngens[i, j])
                    req.generated = None
                    req.finish_time = t
                    self.completed[i].append(req)
                    if tr is not None:
                        tr.event(EV_COMPLETE, req.rid, self._trace_pool,
                                 i, t)
                elif kind == _EV_HANDOFF:
                    req.n_generated = 1
                    req.generated = [int(
                        (np.int64(req.rid) * _LCG_A + self.seeds[i]
                         + _LCG_C) % self.vocab)]
                    req.prefill_done = True
                    req.ready_time = t
                    self.handoff[i].append(req)
                    self.relayed[i].append(req)
                    if tr is not None:
                        tr.event(EV_HANDOFF, req.rid, self._trace_pool, i, t)
                else:                       # overflow / escalation eviction
                    req.generated = None
                    req.prefill_done = False
                    req.preemptions += 1
                    req.ready_time = t
                    req.escalate_at = None
                    if kind == _EV_ESCALATE:
                        req.escalations += 1
                        self.escalated[i].append(req)
                        if tr is not None:
                            tr.event(EV_ESCALATE, req.rid, self._trace_pool,
                                     i, t)
                    else:
                        self.overflowed[i].append(req)
                        if tr is not None:
                            tr.event(EV_OVERFLOW, req.rid, self._trace_pool,
                                     i, t)
