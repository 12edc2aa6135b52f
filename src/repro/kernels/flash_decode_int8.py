"""Pallas TPU flash-decode over an int8-quantized KV cache.

The analytical stack shows int8 KV halves kappa -> doubles n_max -> ~1.7x
tok/W at 64K (one hardware generation, §5.2-beyond).  This kernel is what
makes that real on TPU: K/V live in HBM as int8 with per-(token, head)
f32 scales; dequantization happens inside the VMEM tile, so the HBM stream
is genuinely half of bf16 — an XLA-level dequant would materialise the
bf16 copy and erase the win (same lesson as §Perf iteration A2).  The
cache is head-major like `flash_decode`'s; a per-token scale is applied
to the scores and to the probabilities instead of to the (Tb, D) tiles,
so the scales stay lane-major rows, (1, BLOCK_T), and need no transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def quantize_kv(k: jax.Array, v: jax.Array):
    """Symmetric per-(token, head) int8 quantization.

    k, v: (B, K, T, D) float -> (k_q, v_q int8, k_s, v_s f32 (B, K, T))."""
    def one(x):
        s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
        s = jnp.maximum(s, 1e-8)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                     -127, 127).astype(jnp.int8)
        return q, s
    kq, ks = one(k)
    vq, vs = one(v)
    return kq, vq, ks, vs


_NT = (((1,), (1,)), ((), ()))     # q @ k^T


def _kernel(len_ref, q_ref, kq_ref, vq_ref, ks_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_t: int, n_blocks: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
    # int8 stream from HBM, f32 math in VMEM; the per-token scales
    # (1, Tb) multiply the scores and the probabilities
    k = kq_ref[0, 0].astype(jnp.float32)                 # (Tb, D)
    v = vq_ref[0, 0].astype(jnp.float32)
    k_s, v_s = ks_ref[0, 0], vs_ref[0, 0]                # (1, Tb)
    length = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    s = s * k_s / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    t_idx = t * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t_idx < length, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_new = acc_prev * corr + jnp.dot(p * v_s, v,
                                        preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(t == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_new / jnp.maximum(l_new, 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def flash_decode_int8(q, kq, vq, ks, vs, lengths, *, block_t: int = 256,
                      interpret: bool = True):
    """q: (B,H,D); kq/vq: int8 (B,K,T,D) head-major; ks/vs: f32 (B,K,T);
    lengths: (B,).  Returns (B,H,D).  On a TPU block_t is a multiple of
    128 (int8 tiles are 32 rows; the scale rows are 128 lanes)."""
    B, H, D = q.shape
    K, T = kq.shape[1], kq.shape[2]
    G = H // K
    block_t = min(block_t, T)
    n_blocks = -(-T // block_t)
    pad = n_blocks * block_t - T
    if pad:
        kq = jnp.pad(kq, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vq = jnp.pad(vq, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad)))
    qh = q.reshape(B, K, G, D)
    kernel = functools.partial(_kernel, block_t=block_t, n_blocks=n_blocks)
    kv_spec = pl.BlockSpec((1, 1, block_t, D), lambda b, h, t: (b, h, t, 0))
    sc_spec = pl.BlockSpec((1, 1, 1, block_t), lambda b, h, t: (b, h, 0, t))
    out = pl.pallas_call(
        kernel,
        grid=(B, K, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
            kv_spec, kv_spec, sc_spec, sc_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qh, kq, vq, ks[:, :, None, :],
      vs[:, :, None, :])
    return out.reshape(B, H, D)
