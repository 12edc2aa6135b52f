"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler installed with JAX compiles
for a v5e that is described, not attached, and refuses what the chip
would refuse (tile alignment, VMEM use, unsupported ops).  Covered: the
four Pallas kernels at real model widths, which must lower to a Mosaic
`tpu_custom_call`, and two signatures of the fleet drain with its
float64 meters, one at the hybrid binding's 1024-slot width.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.  The persistent compilation cache is off around these
compiles; a cache entry compiled for a described chip cannot be read
back without one.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.modelspec import LLAMA31_70B
from repro.core.profiles import H100_LLAMA70B
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_decode_int8 import flash_decode_int8
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.wkv6 import wkv6
from repro.models.compat import enable_x64
from repro.serving import jax_engine


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                 # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _kernel_case(name):
    """(fn, arg shapes) at real widths: flash-decode at B=8, H=64, K=8,
    D=128, T=8192 in bf16; zamba2-2.7b's SSD scan (80 heads x 64, state
    64); rwkv6-1.6b's WKV (32 heads x 64)."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    B, H, K, D, T = 8, 64, 8, 128, 8192
    if name == "flash_decode":
        return (lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False),
                [((B, H, D), bf16), ((B, K, T, D), bf16),
                 ((B, K, T, D), bf16), ((B,), i32)])
    if name == "flash_decode_int8":
        return (lambda q, kq, vq, ks, vs, n: flash_decode_int8(
                    q, kq, vq, ks, vs, n, interpret=False),
                [((B, H, D), bf16), ((B, K, T, D), jnp.int8),
                 ((B, K, T, D), jnp.int8), ((B, K, T), f32),
                 ((B, K, T), f32), ((B,), i32)])
    if name == "mamba_scan":
        b, nh, S, hd, ds = 2, 80, 2048, 64, 64
        return (lambda x, bm, cm, la: mamba_scan(x, bm, cm, la,
                                                 interpret=False),
                [((b, nh, S, hd), f32), ((b, S, ds), f32),
                 ((b, S, ds), f32), ((b, nh, S), f32)])
    b, nh, S, hd = 2, 32, 2048, 64
    return (lambda r, k, v, w, u: wkv6(r, k, v, w, u, interpret=False),
            [((b, nh, S, hd), f32)] * 4 + [((nh, hd), f32)])


@pytest.mark.parametrize("name", ["flash_decode", "flash_decode_int8",
                                  "mamba_scan", "wkv6"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (I instances, S slots, Q queue entries): a small signature, and the
# hybrid binding's 8K pool padded as its cell runs it
DRAIN_SHAPES = {"small": (32, 16, 8), "hybrid_8k": (8, 1024, 2048)}


@pytest.mark.parametrize("shape", sorted(DRAIN_SHAPES))
def test_fleet_drain_compiles_for_v5e(shape, one_chip):
    """A decode drain signature, float64 meters included; on the TPU its
    row lookups are selects, so no gather is left, and every prefix scan
    (a reduce-window) is over s32: an f64 or s64 one is emulated in
    O(S^2) adds and takes minutes to compile at S=1024."""
    I, S, Q = DRAIN_SHAPES[shape]
    eng = jax_engine.JaxPoolEngine(
        instances=I, window=4096, n_slots=S, profile=H100_LLAMA70B,
        streamed_params=LLAMA31_70B.streamed_params, prefill_chunk=512,
        respect_arrival=True)
    with enable_x64():
        args = {k: jax.ShapeDtypeStruct(
                    (I, Q) if np.ndim(a) == 2 else np.shape(a),
                    np.asarray(a).dtype, sharding=one_chip)
                for k, a in eng._pack(max_iters=1000).items()}
        compiled = jax_engine._drain.lower(
            args, phase="decode", n_slots_pad=S, platform="tpu").compile()
    text = compiled.as_text()
    assert "f64" in text
    assert " gather(" not in text
    scans = re.findall(r"= (.*?) reduce-window\(", text)
    assert scans
    dtypes = {d for t in scans for d in re.findall(r"(\w+)\[", t)}
    assert dtypes == {"s32"}, scans
