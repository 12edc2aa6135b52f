"""Pallas TPU flash-decode kernel — the paper's H(L)*n KV-scan term.

Decode attention is memory-bound: per iteration every sequence streams its
whole KV cache (kappa * L bytes) HBM -> VMEM once.  This kernel expresses
that stream explicitly: grid = (batch, kv_head, kv_blocks) with the KV-block
dimension innermost/sequential, carrying online-softmax state (m, l, acc) in
VMEM scratch.  The cache is laid out head-major, (B, K, T, D), so every KV
block is a (BLOCK_T, D) tile that meets the TPU's (8, 128) tiling; the
G = H/K query heads of a GQA group ride along in one tile so each KV block
is read exactly once per group (not per head) — the TPU-native adaptation
of TP-sharded GQA decode (DESIGN.md §3).  The per-sequence lengths sit in
SMEM whole, indexed by the batch grid position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 256
NEG_INF = -1e30

# q @ k^T: contract the head dim of both operands
_NT = (((1,), (1,)), ((), ()))


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, block_t: int,
                         n_blocks: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)              # (Tb, D)
    v = v_ref[0, 0].astype(jnp.float32)              # (Tb, D)
    length = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    t_idx = t * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t_idx < length, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_new = acc_prev * corr + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)

    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(t == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_new / jnp.maximum(l_new, 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, *, block_t: int = DEFAULT_BLOCK_T,
                 interpret: bool = True) -> jax.Array:
    """q: (B, H, D); k, v: (B, K, T, D) head-major; lengths: (B,)
    -> (B, H, D).

    interpret=True executes the kernel body in Python on CPU; on a real
    TPU pass interpret=False (block_t a multiple of 8, or of 16 in bf16).
    """
    B, H, D = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    block_t = min(block_t, T)
    n_blocks = -(-T // block_t)
    pad_t = n_blocks * block_t - T
    if pad_t:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    qh = q.reshape(B, K, G, D)

    kernel = functools.partial(_flash_decode_kernel, block_t=block_t,
                               n_blocks=n_blocks)
    kv_spec = pl.BlockSpec((1, 1, block_t, D), lambda b, h, t: (b, h, t, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, K, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),     # m
            pltpu.VMEM((G, 1), jnp.float32),     # l
            pltpu.VMEM((G, D), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qh, k, v)
    return out.reshape(B, H, D)
