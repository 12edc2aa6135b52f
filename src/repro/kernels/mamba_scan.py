"""Pallas TPU chunked-SSD scan (Mamba2) — recurrent-state hot path.

TPU adaptation of the GPU SSD algorithm: instead of warp-level scans, the
chunk dimension is the innermost *sequential* grid axis with the (hd, ds)
state carried in VMEM scratch; intra-chunk work is MXU matmuls ((Lc x Lc)
decay-masked attention-like product and the state outer-product update).
Inputs are head-major, (B, nh, S, hd), so every block is an (Lc, hd) tile
that meets the (8, 128) tiling; the per-head log-decays arrive as one
(1, Lc) row per chunk, and their in-chunk cumulative sums are triangular
matmuls (there is no scan primitive inside a TPU kernel).
grid = (batch, heads, chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128

_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b
_EXACT = jax.lax.Precision.HIGHEST


def _mamba_kernel(xt_ref, b_ref, c_ref, la_ref, y_ref, fin_ref, st_ref,
                  *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    f32 = jnp.float32
    xt = xt_ref[0, 0].astype(f32)                  # (Lc, hd)
    bm = b_ref[0].astype(f32)                      # (Lc, ds)
    cm = c_ref[0].astype(f32)                      # (Lc, ds)
    la = la_ref[0, 0].astype(f32)                  # (1, Lc)
    state = st_ref[...]                            # (hd, ds)

    Lc = xt.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    tri = row >= col                               # (q, t): t <= q
    la_rows = jnp.broadcast_to(la, (Lc, Lc))       # [r, t] = la[t]
    # inclusive cumsum cs, as cs[q] down the rows and cs[t] along them
    cs_q = jax.lax.dot_general(tri.astype(f32), la_rows, _NT,
                               precision=_EXACT, preferred_element_type=f32)
    cs_t = jnp.dot(la_rows, (row <= col).astype(f32), precision=_EXACT,
                   preferred_element_type=f32)
    diff = cs_q - cs_t                             # (q, t)
    G = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    att = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=f32) * G
    y_intra = jnp.dot(att, xt, preferred_element_type=f32)       # (q, hd)
    y_inter = jnp.exp(cs_q[:, :1]) * jax.lax.dot_general(
        cm, state, _NT, preferred_element_type=f32)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    total = jnp.sum(la, axis=1, keepdims=True)     # (1, 1) = cs[-1]
    dec = jnp.exp(total - cs_q[:, :1])             # (t, 1)
    st_new = state * jnp.exp(total) + jax.lax.dot_general(
        dec * xt, bm, _TN, preferred_element_type=f32)
    st_ref[...] = st_new

    @pl.when(ci == n_chunks - 1)
    def _finish():
        fin_ref[0, 0] = st_new.astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan(xt: jax.Array, Bm: jax.Array, Cm: jax.Array, lA: jax.Array,
               *, chunk: int = DEFAULT_CHUNK, interpret: bool = True):
    """Chunked SSD scan, head-major.

    xt: (B,nh,S,hd) dt-scaled inputs; Bm/Cm: (B,S,ds); lA: (B,nh,S).
    Returns (y (B,nh,S,hd), final_state (B,nh,hd,ds)).  On a TPU the
    chunk is a multiple of 128 (the log-decay rows are lane-major).
    """
    B, nh, S, hd = xt.shape
    ds = Bm.shape[-1]
    Lc = min(chunk, S)
    n_chunks = -(-S // Lc)
    pad = n_chunks * Lc - S
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        lA = jnp.pad(lA, ((0, 0), (0, 0), (0, pad)))

    kernel = functools.partial(_mamba_kernel, n_chunks=n_chunks)
    y, fin = pl.pallas_call(
        kernel,
        grid=(B, nh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, Lc, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, Lc, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Lc, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 1, Lc), lambda b, h, c: (b, h, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Lc, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, n_chunks * Lc, hd), xt.dtype),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=interpret,
    )(xt, Bm, Cm, lA[:, :, None, :])
    return y[:, :, :S], fin
