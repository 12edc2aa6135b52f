"""int8-KV flash decode: quantize -> kernel vs float reference, plus
quantization-error bounds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode_int8 import flash_decode_int8, quantize_kv
from repro.kernels.ref import flash_decode_ref


@pytest.mark.parametrize("B,H,K,D,T,bt", [
    (2, 8, 4, 64, 100, 64), (1, 4, 2, 128, 300, 128), (3, 2, 2, 32, 50, 16),
])
def test_int8_flash_decode(B, H, K, D, T, bt):
    rng = jax.random.PRNGKey(B + T)
    ks_ = jax.random.split(rng, 4)
    q = jax.random.normal(ks_[0], (B, H, D))
    k = jax.random.normal(ks_[1], (B, T, K, D))
    v = jax.random.normal(ks_[2], (B, T, K, D))
    lengths = jax.random.randint(ks_[3], (B,), 1, T + 1)
    # the kernel reads the head-major (B, K, T, D) cache
    kq, vq, ks8, vs8 = quantize_kv(k.swapaxes(1, 2), v.swapaxes(1, 2))
    out = flash_decode_int8(q, kq, vq, ks8, vs8, lengths, block_t=bt)
    ref = flash_decode_ref(q, k, v, lengths)
    # int8 KV quantization error: attention output within ~1% relative
    denom = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) / denom < 0.02


def test_quantize_roundtrip_error():
    rng = jax.random.PRNGKey(0)
    k = jax.random.normal(rng, (2, 4, 64, 64)) * 3.0
    kq, _, ks, _ = quantize_kv(k, k)
    deq = kq.astype(jnp.float32) * ks[..., None]
    rel = float(jnp.abs(deq - k).max() / jnp.abs(k).max())
    assert rel < 0.01            # 127-level symmetric quant
    assert kq.dtype == jnp.int8
    # the capacity lever: int8 cache is half the bytes of bf16
    assert kq.nbytes + ks.astype(jnp.bfloat16).nbytes \
        < 0.55 * k.astype(jnp.bfloat16).nbytes


def test_int8_matches_fp_kernel_when_exact():
    """With power-of-two values the quantization is exact and the int8
    kernel must agree with the float kernel bit-for-bit-ish."""
    from repro.kernels.flash_decode import flash_decode
    B, H, K, D, T = 1, 2, 2, 32, 40
    rng = jax.random.PRNGKey(3)
    q = jax.random.normal(rng, (B, H, D))
    base = jnp.sign(jax.random.normal(rng, (B, K, T, D)))  # +-1 exact
    lengths = jnp.array([T])
    kq, vq, ks, vs = quantize_kv(base, base)
    a = flash_decode_int8(q, kq, vq, ks, vs, lengths, block_t=16)
    b = flash_decode(q, base, base, lengths, block_t=16)
    np.testing.assert_allclose(a, b, atol=1e-5)
