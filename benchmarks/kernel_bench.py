"""Kernel micro-benchmarks (CPU: jnp reference path timing + interpret-mode
validation cost; real-TPU numbers require hardware — see EXPERIMENTS.md)."""
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.attention import flash_attention


def _time(fn, *args, iters=5):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run():
    rng = jax.random.PRNGKey(0)
    rows = []
    # decode attention: B=8 sequences, 4K cache, GQA 8/2
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (8, 8, 64))
    k = jax.random.normal(ks[1], (8, 2, 4096, 64))
    v = jax.random.normal(ks[2], (8, 2, 4096, 64))
    lengths = jnp.full((8,), 4096)
    f_ref = jax.jit(lambda *a: ops.decode_attention(*a, force="ref"))
    rows.append(dict(name="decode_attention_ref_b8_t4096",
                     us_per_call=_time(f_ref, q, k, v, lengths),
                     derived="kv_bytes=%d" % (k.nbytes + v.nbytes)))
    # prefill flash attention 1x1024
    q2 = jax.random.normal(ks[0], (1, 1024, 8, 64))
    k2 = jax.random.normal(ks[1], (1, 1024, 2, 64))
    f_fa = jax.jit(lambda a, b, c: flash_attention(a, b, c, q_chunk=256,
                                                   kv_chunk=256))
    rows.append(dict(name="flash_attention_1x1024",
                     us_per_call=_time(f_fa, q2, k2, k2),
                     derived="flops=%.2e" % (4 * 1024 * 1024 * 8 * 64)))
    # ssm scans
    xt = jax.random.normal(ks[0], (2, 4, 512, 64))
    Bm = jax.random.normal(ks[1], (2, 512, 64))
    lA = -jnp.abs(jax.random.normal(ks[2], (2, 4, 512)))
    f_ssd = jax.jit(lambda *a: ops.ssd_scan(*a, force="ref"))
    rows.append(dict(name="ssd_scan_ref_2x512",
                     us_per_call=_time(f_ssd, xt, Bm, Bm, lA),
                     derived="state=(4,64,64)"))
    r = jax.random.normal(ks[0], (2, 4, 256, 64))
    w = jnp.exp(-jnp.exp(-6 + 0.1 * jax.random.normal(ks[1],
                                                      (2, 4, 256, 64))))
    u = jnp.ones((4, 64)) * 0.5
    f_wkv = jax.jit(lambda *a: ops.wkv_scan(*a, force="ref"))
    rows.append(dict(name="wkv6_ref_2x256",
                     us_per_call=_time(f_wkv, r, r, r, w, u),
                     derived="state=(4,64,64)"))
    return rows, "CPU reference-path timings (TPU kernels validated in interpret mode)"
