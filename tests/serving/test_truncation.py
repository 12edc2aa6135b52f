"""Drain-cap truncation must fail loudly.  Before the fix, every drive
loop (`PoolEngine.run_until_drained`, `BatchedPoolEngine.run_until_drained`
and the router path over them) hit `max_iters` and *returned as if
drained*: queued requests silently vanished and the meters rolled
under-counted tokens/energy straight into fleet tok/W.  Now a busy pool
at the cap raises `DrainTruncatedError`."""
import math
import numpy as np
import pytest

from repro.core.modelspec import LLAMA31_70B
from repro.core.profiles import H100_LLAMA70B
from repro.serving import (BatchedPoolEngine, ContextRouter,
                           DrainTruncatedError, PoolEngine, Request,
                           RouterPolicy)

STREAMED = LLAMA31_70B.streamed_params


def _reqs(n=40):
    return [Request(rid=i, prompt=np.broadcast_to(np.int64(0), (700,)),
                    max_new_tokens=60, arrival_time=0.01 * i)
            for i in range(n)]


def test_scalar_engine_raises_on_truncated_drain():
    eng = PoolEngine(None, None, profile=H100_LLAMA70B,
                     streamed_params=STREAMED, window=4096,
                     prefill_chunk=256, respect_arrival=True)
    for r in _reqs():
        eng.submit(r)
    with pytest.raises(DrainTruncatedError, match="max_iters=3"):
        eng.run_until_drained(max_iters=3)
    eng.run_until_drained(max_iters=200_000)   # recoverable: finish it
    assert not eng.busy


def test_batched_engine_raises_on_truncated_drain():
    eng = BatchedPoolEngine(instances=2, window=4096,
                            profile=H100_LLAMA70B,
                            streamed_params=STREAMED, prefill_chunk=256,
                            respect_arrival=True)
    for i, r in enumerate(_reqs()):
        eng.submit(r, i % 2)
    eng.sort_queues()
    with pytest.raises(DrainTruncatedError) as ei:
        eng.run_until_drained(max_iters=3)
    assert ei.value.max_iters == 3
    eng.run_until_drained(max_iters=200_000)
    assert not eng.busy


def test_router_propagates_truncation():
    pool = PoolEngine(None, None, profile=H100_LLAMA70B,
                      streamed_params=STREAMED, window=8192,
                      prefill_chunk=256, respect_arrival=True,
                      name="only")
    router = ContextRouter({"only": pool},
                           RouterPolicy(ladder=[("only", math.inf)]))
    with pytest.raises(DrainTruncatedError):
        router.run(_reqs(), max_iters=3)
