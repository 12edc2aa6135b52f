"""Pallas kernels (interpret=True) vs pure-jnp oracles: shape/dtype sweeps.

Per the brief: for each kernel, sweep shapes/dtypes and assert_allclose
against the ref.py oracle.  The kernels take head-major inputs
(B, heads, S, hd); the oracles are sequence-major (B, S, heads, hd), so
each case draws sequence-major data and hands the kernel `hm(x)`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep; see requirements-dev.txt
from hypothesis import given, settings, strategies as st

from repro.kernels import flash_decode, mamba_scan, wkv6
from repro.kernels import ops
from repro.kernels.ref import flash_decode_ref, mamba_scan_ref, wkv6_ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 5e-2}


def hm(x):
    """Sequence-major <-> head-major (swaps axes 1 and 2)."""
    return x.swapaxes(1, 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,D,T,bt", [
    (2, 8, 4, 64, 100, 64), (1, 16, 8, 128, 300, 256),
    (3, 4, 4, 32, 64, 16), (1, 4, 1, 128, 513, 128),
])
def test_flash_decode_sweep(B, H, K, D, T, bt, dtype):
    rng = jax.random.PRNGKey(B * 7 + T)
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, K, D), dtype)
    v = jax.random.normal(ks[2], (B, T, K, D), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    out = flash_decode(q, hm(k), hm(v), lengths, block_t=bt)
    ref = flash_decode_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), lengths)
    np.testing.assert_allclose(out.astype(jnp.float32), ref,
                               atol=ATOL[dtype], rtol=1e-2)


@settings(max_examples=10, deadline=None)
@given(T=st.integers(1, 200), B=st.integers(1, 3))
def test_flash_decode_lengths_property(T, B):
    """Entries beyond `lengths` must not influence the output."""
    rng = jax.random.PRNGKey(T)
    ks = jax.random.split(rng, 4)
    H = K = 2
    D = 16
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, T, K, D))
    v = jax.random.normal(ks[2], (B, T, K, D))
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    out1 = flash_decode(q, hm(k), hm(v), lengths, block_t=32)
    mask = jnp.arange(T)[None, :, None, None] < lengths[:, None, None, None]
    k2 = jnp.where(mask, k, 999.0)   # garbage outside the valid range
    v2 = jnp.where(mask, v, -999.0)
    out2 = flash_decode(q, hm(k2), hm(v2), lengths, block_t=32)
    np.testing.assert_allclose(out1, out2, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,nh,hd,ds,ch", [
    (2, 64, 3, 32, 16, 32), (1, 100, 2, 64, 64, 32), (1, 16, 1, 8, 8, 16),
])
def test_mamba_scan_sweep(B, S, nh, hd, ds, ch, dtype):
    rng = jax.random.PRNGKey(S)
    ks = jax.random.split(rng, 4)
    xt = jax.random.normal(ks[0], (B, S, nh, hd), dtype)
    Bm = jax.random.normal(ks[1], (B, S, ds), dtype)
    Cm = jax.random.normal(ks[2], (B, S, ds), dtype)
    lA = -jnp.abs(jax.random.normal(ks[3], (B, S, nh))) * 0.5
    y, st_ = mamba_scan(hm(xt), Bm, Cm, lA.swapaxes(1, 2), chunk=ch)
    y = hm(y)
    yr, sr = mamba_scan_ref(xt.astype(jnp.float32), Bm.astype(jnp.float32),
                            Cm.astype(jnp.float32), lA)
    np.testing.assert_allclose(y.astype(jnp.float32), yr,
                               atol=ATOL[dtype] * 20, rtol=5e-2)
    np.testing.assert_allclose(st_, sr, atol=ATOL[dtype] * 20, rtol=5e-2)


@pytest.mark.parametrize("wmin", [0.05, 0.8])
@pytest.mark.parametrize("B,S,H,hd,ch", [
    (2, 64, 2, 32, 32), (1, 100, 3, 64, 64), (1, 7, 1, 8, 16),
])
def test_wkv6_sweep(B, S, H, hd, ch, wmin):
    """Including strong decay (w -> 0.05): the exact pairwise-difference
    formulation must stay finite where the factored form would overflow."""
    rng = jax.random.PRNGKey(S + H)
    ks = jax.random.split(rng, 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) for i in range(3))
    w = jax.random.uniform(ks[3], (B, S, H, hd), minval=wmin, maxval=1.0)
    u = 0.5 * jax.random.normal(ks[4], (H, hd))
    y, st_ = wkv6(hm(r), hm(k), hm(v), hm(w), u, chunk=ch)
    y = hm(y)
    yr, sr = wkv6_ref(r, k, v, w, u)
    assert jnp.isfinite(y).all()
    np.testing.assert_allclose(y, yr, atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(st_, sr, atol=2e-3, rtol=1e-3)


def test_ops_dispatch_modes():
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (2, 4, 32))
    k = jax.random.normal(ks[1], (2, 2, 50, 32))
    v = jax.random.normal(ks[2], (2, 2, 50, 32))
    lengths = jnp.array([50, 13])
    a = ops.decode_attention(q, k, v, lengths, force="ref")
    b = ops.decode_attention(q, k, v, lengths, force="interpret")
    np.testing.assert_allclose(a, b, atol=1e-5)
    assert ops._mode(None) == "ref"   # CPU container default
