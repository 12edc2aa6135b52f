"""Pallas TPU chunked WKV6 kernel (RWKV6 data-dependent-decay recurrence).

Same sequential-chunk-grid structure as mamba_scan, with per-channel decay.
The intra-chunk pairwise decay exp(cw_ex[t] - cw[s]) is exponentiated as a
difference, never factored — exact and overflow-safe for any w in (0, 1]
(the factored qd/kd form overflows f32 once cumulative in-chunk decay
exceeds ~e^88; see tests/kernels sweeps).  It is built one query row at a
time, an (Lc, hd) tile per row, so the kernel never holds an (Lc, Lc, hd)
tensor.  Inputs are head-major, (B, H, S, hd); in-chunk cumulative sums are
triangular matmuls.  grid = (batch, heads, chunks); state (hd_k, hd_v)
lives in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64

_TN = (((0,), (0,)), ((), ()))     # a^T @ b
_EXACT = jax.lax.Precision.HIGHEST


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, fin_ref, st_ref,
                *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    f32 = jnp.float32
    r = r_ref[0, 0].astype(f32)                    # (Lc, hd)
    k = k_ref[0, 0].astype(f32)
    v = v_ref[0, 0].astype(f32)
    lw = lw_ref[0, 0].astype(f32)
    u = u_ref[0].astype(f32)                       # (1, hd)
    state = st_ref[...]                            # (hd_k, hd_v)

    Lc, hd = r.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    cw = jnp.dot((row >= col).astype(f32), lw, precision=_EXACT,
                 preferred_element_type=f32)       # inclusive (Lc, hd)
    cw_ex = cw - lw                                # exclusive

    y_inter = jnp.dot(r * jnp.exp(cw_ex), state,
                      preferred_element_type=f32)  # (Lc, hd_v)

    # exact pairwise decay, one query row t at a time: exponent <= 0 for
    # the sources s < t that the mask keeps
    src = jax.lax.broadcasted_iota(jnp.int32, (Lc, 1), 0)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (Lc, hd), 0)
    y_intra = jnp.zeros((Lc, hd), f32)
    for t in range(1, Lc):
        dec = jnp.where(src < t, jnp.exp(cw_ex[t:t + 1] - cw), 0.0)
        att = jnp.sum(dec * k * r[t:t + 1], axis=1, keepdims=True)  # (s, 1)
        y_t = jnp.sum(att * v, axis=0, keepdims=True)               # (1, hd)
        y_intra = jnp.where(out_row == t, y_t, y_intra)

    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    y_ref[0, 0] = (y_inter + y_intra + bonus).astype(y_ref.dtype)

    w_last = cw[Lc - 1:Lc]                         # (1, hd)
    kdec = k * jnp.exp(w_last - cw)                # exponent <= 0
    # state rows (the key channel) decay by exp(w_last): diag(.) @ state
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1))
    decay = jnp.where(eye, jnp.exp(w_last), 0.0)
    st_new = jnp.dot(decay, state, precision=_EXACT,
                     preferred_element_type=f32) + jax.lax.dot_general(
        kdec, v, _TN, preferred_element_type=f32)
    st_ref[...] = st_new

    @pl.when(ci == n_chunks - 1)
    def _finish():
        fin_ref[0, 0] = st_new.astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, *, chunk: int = DEFAULT_CHUNK,
         interpret: bool = True):
    """r,k,v,w: (B,H,S,hd) head-major; u: (H,hd).
    Returns (out (B,H,S,hd), final_state (B,H,hd,hd))."""
    B, H, S, hd = r.shape
    Lc = min(chunk, S)
    n_chunks = -(-S // Lc)
    pad = n_chunks * Lc - S

    def padt(a, fill=0.0):
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)),
                       constant_values=fill) if pad else a

    r_, k_, v_ = padt(r), padt(k), padt(v)
    lw = jnp.log(jnp.maximum(padt(w, fill=1.0), 1e-30))

    kernel = functools.partial(_wkv_kernel, n_chunks=n_chunks)
    spec = pl.BlockSpec((1, 1, Lc, hd), lambda b, h, c: (b, h, c, 0))
    y, fin = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0))],
        out_specs=[spec,
                   pl.BlockSpec((1, 1, hd, hd),
                                lambda b, h, c: (b, h, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n_chunks * Lc, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r_, k_, v_, lw, u[:, None, :])
    return y[:, :, :S], fin
