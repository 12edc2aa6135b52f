"""Host spans and counters the benchmark takes from the program, and the
compile log.

`Spans.install(program)` wraps program methods from outside, for the traced
run only: each call is kept in memory as (name, start, end) on the host
clock and is also written into the profiler's trace as a
`jax.profiler.TraceAnnotation`, so idle gaps of the device can be laid
against what the host was doing.  The `_finalize` wrapper also reads the
drain's own iteration counter (`it`) from the results it replays.

A rename of any wrapped method turns the metrics that read it null: the
wrapper then finds nothing to wrap and says so on stderr.
"""
from __future__ import annotations

import contextlib
import functools
import sys
import time
from typing import Callable, Dict, List, Tuple

import jax

# (module under the program root, class or None, function): span name
WRAPPED = (
    ("serving.fleetsim", None, "prepare_spec"),
    ("serving.fleetsim", "FleetSim", "begin_run"),
    ("serving.fleetsim", "FleetSim", "pre_role"),
    ("serving.fleetsim", "FleetSim", "drain_role"),
    ("serving.fleetsim", "FleetSim", "finish_run"),
    ("serving.jax_engine", None, "drain_engines"),
    ("serving.jax_engine", "JaxPoolEngine", "_pack"),
    ("serving.jax_engine", "JaxPoolEngine", "_finalize"),
)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Spans:
    """Spans and counters of one traced run."""

    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.counters: Dict[str, int] = {}
        self._undo: List[Callable[[], None]] = []
        self._groups: list = []

    def _wrap(self, owner, attr: str, name: str, after=None) -> None:
        raw = owner.__dict__[attr]
        fn = getattr(owner, attr)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                t0 = time.perf_counter()
                try:
                    out = fn(*args, **kwargs)
                finally:
                    self.spans.append((name, t0, time.perf_counter()))
            if after is not None:
                after(args, kwargs)
            return out

        setattr(owner, attr,
                staticmethod(wrapper) if isinstance(raw, staticmethod)
                else wrapper)
        self._undo.append(lambda: setattr(owner, attr, raw))

    def install(self, resolve: Callable[[str], object]) -> None:
        """Wrap every method of `WRAPPED`; `resolve(module)` imports a
        module of the program."""
        for mod_name, cls_name, fn_name in WRAPPED:
            mod = resolve(mod_name)
            owner = getattr(mod, cls_name, None) if cls_name else mod
            name = f"{cls_name}.{fn_name}" if cls_name else fn_name
            if owner is None or fn_name not in vars(owner):
                print(f"bench: nothing to wrap at {mod_name}.{name}",
                      file=sys.stderr)
                continue
            after = self._count_iters if fn_name == "_finalize" else None
            self._wrap(owner, fn_name, name, after)

    def _count_iters(self, args, kwargs) -> None:
        """Add the drain's iteration counter `it`, once per compiled group:
        every engine of a group is handed the same 0-d array."""
        res = args[1] if len(args) > 1 else kwargs.get("res")
        it = None if res is None else res.get("it")
        if it is None or any(it is x for x in self._groups):
            return
        self._groups.append(it)
        self.counters["drain_iters"] = \
            self.counters.get("drain_iters", 0) + int(it)
        self.counters["drain_groups"] = len(self._groups)

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()


class CompileLog:
    """Backend compiles and programs loaded from the persistent compile
    cache, from JAX's monitoring events (as the repo's chip_smoke.py reads
    them): none may happen inside the window."""

    def __init__(self):
        self.programs: List[str] = []
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **kw):
        if event == _BACKEND_COMPILE:
            self.programs.append(kw.get("fun_name", ""))

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.programs.append("(from the compile cache)")

    def close(self):
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_listener(self._event)


@contextlib.contextmanager
def installed(spans: Spans, resolve):
    spans.install(resolve)
    try:
        yield spans
    finally:
        spans.uninstall()
