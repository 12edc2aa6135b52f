"""The control and the planted faults that the comparison must fail.

None of these runs in a benchmark run.  `bench/calibrate.py` reads them on
the chip at a cell's own size to set the limits, and `tests/bench` drives a
whole run with each of them at a small size on the CPU.

  float32      the control: the drain with its float64 meters and clock
               in float32 (the drain's x64 scope left out), the step below
               the precision the program states;
  unchanged    every drain returns its initial state (the loop never
               steps);
  half_batch   half of every pool's instances get an empty queue: their
               requests are never drained;
  no_exchange  overflow migrations between pools are dropped (the
               exchange between a short pool and the long pool);
  altered      one request's finish time is moved by a millisecond where
               the drain produces it.

Each is a context manager over the program's modules.
"""
from __future__ import annotations

import contextlib

import numpy as np

from . import cells


def _jax_engine():
    return cells.resolve(cells.PROGRAM_ROOT, "serving.jax_engine")


@contextlib.contextmanager
def _patched(owner, attr, value):
    old = owner.__dict__[attr]
    setattr(owner, attr, value)
    try:
        yield
    finally:
        setattr(owner, attr, old)


def float32():
    return _patched(_jax_engine(), "enable_x64", contextlib.nullcontext)


def unchanged():
    je = _jax_engine()
    drain = je._drain

    def no_steps(p, **kw):
        return drain(dict(p, max_iters=np.int32(0)), **kw)
    return _patched(je, "_drain", no_steps)


def half_batch():
    cls = _jax_engine().JaxPoolEngine
    pack = cls._pack

    def half(self, max_iters):
        params = pack(self, max_iters)
        params["qlen"] = params["qlen"].copy()
        params["qlen"][1::2] = 0
        self.qlen[1::2] = 0
        return params
    return _patched(cls, "_pack", half)


def no_exchange():
    cls = _jax_engine().JaxPoolEngine
    finalize = cls._finalize

    def drop(self, res, max_iters):
        finalize(self, res, max_iters)
        for lst in self.overflowed:
            lst.clear()
    return _patched(cls, "_finalize", drop)


def altered():
    cls = _jax_engine().JaxPoolEngine
    finalize = cls._finalize

    def move(self, res, max_iters):
        done = np.argwhere(res["out_kind"] == 1)
        if len(done):
            res = dict(res, out_time=res["out_time"].copy())
            res["out_time"][tuple(done[0])] += 1e-3
        finalize(self, res, max_iters)
    return _patched(cls, "_finalize", move)


CONTROLS = dict(float32=float32, unchanged=unchanged, half_batch=half_batch,
                no_exchange=no_exchange, altered=altered)
