"""Backend-dispatching wrappers around the Pallas kernels.

On TPU backends the compiled Pallas kernels run (interpret=False); on CPU
the default is the pure-jnp reference so jit/grad/vmap all
work at full speed, with `force="interpret"` available to execute the actual
kernel bodies for validation (tests/kernels does exactly that).

  force=None         backend-based dispatch
  force="pallas"     compiled kernel (TPU only)
  force="interpret"  Pallas interpret mode (CPU-executable kernel body)
  force="ref"        pure-jnp oracle

Every wrapper takes the kernels' head-major layout; the sequence-major
oracles in `kernels.ref` get a transposed view.  `REPRO_FORCE_KERNEL`
sets `force` for a whole process.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from . import flash_decode as _fd
from . import mamba_scan as _ms
from . import ref as _ref
from . import wkv6 as _wk


def _mode(force: Optional[str]) -> str:
    force = force or os.environ.get("REPRO_FORCE_KERNEL") or None
    if force:
        return force
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def decode_attention(q, k, v, lengths, *, block_t: int = 256,
                     force: Optional[str] = None):
    """(B,H,D) x (B,K,T,D) -> (B,H,D); the tau = W + H(L)n KV-scan."""
    m = _mode(force)
    if m == "ref":
        return _ref.flash_decode_ref(q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                                     lengths)
    return _fd.flash_decode(q, k, v, lengths, block_t=block_t,
                            interpret=(m != "pallas"))


def ssd_scan(xt, Bm, Cm, lA, *, chunk: int = 128,
             force: Optional[str] = None):
    """xt (B,nh,S,hd), Bm/Cm (B,S,ds), lA (B,nh,S) -> (y, final state)."""
    m = _mode(force)
    if m == "ref":
        y, fin = _ref.mamba_scan_ref(xt.swapaxes(1, 2), Bm, Cm,
                                     lA.swapaxes(1, 2))
        return y.swapaxes(1, 2), fin
    return _ms.mamba_scan(xt, Bm, Cm, lA, chunk=chunk,
                          interpret=(m != "pallas"))


def wkv_scan(r, k, v, w, u, *, chunk: int = 64,
             force: Optional[str] = None):
    """r, k, v, w (B,H,S,hd), u (H,hd) -> (y, final state)."""
    m = _mode(force)
    if m == "ref":
        y, fin = _ref.wkv6_ref(*(a.swapaxes(1, 2) for a in (r, k, v, w)), u)
        return y.swapaxes(1, 2), fin
    return _wk.wkv6(r, k, v, w, u, chunk=chunk,
                    interpret=(m != "pallas"))
