"""Grep-style lint for the repo's structural invariants.

Fast (no imports of the package, pure text scan) so CI can run it as a
seconds-long job on every PR.  Three invariants, each easy to erode one
convenient line at a time:

1. **Topology kind dispatch is centralised.**  String-kind topology
   dispatch (``kind == "fleetopt"`` etc.) exists in exactly one place:
   ``TopologySpec.from_kind`` in ``src/repro/core/topospec.py``, the
   legacy-kind -> IR compiler.  Everything downstream consumes the IR.
   A new ``if kind == ...`` anywhere else reintroduces the scattered
   dispatch the IR removed.  (Only *topology* kind literals are
   flagged — block kinds like ``b.kind == "attn"`` in repro.models and
   shape kinds like ``shape.kind == "train"`` in repro.launch are
   different enums and exempt by literal, not by path.)

2. **JAX mesh-context APIs are quarantined.**  The mesh-context API
   surface (``get_abstract_mesh`` / ``set_mesh`` / ``use_mesh`` /
   ``AxisType``) is version-dependent across jax releases; the repo
   funnels every touch through ``repro.models.compat``.  Importing or
   referencing those names from ``jax.sharding`` anywhere else breaks
   one of the two supported jax versions.  (Importing the shims *from*
   ``repro.models.compat`` is of course the sanctioned path and not
   flagged; stable names like ``NamedSharding``/``PartitionSpec`` are
   fine anywhere.)

3. **Serving telemetry goes through TraceRecorder.**  The engine hot
   loops (everything under ``src/repro/serving/``) emit observability
   through the FleetScope recorder (``serving.telemetry``) — that is
   what keeps the zero-overhead-when-off guarantee auditable.  An
   ad-hoc ``print(...)`` in the serving stack is either debug residue
   or a new side channel the trace schema doesn't know about; both are
   flagged.  (Benchmarks, tools and examples print freely — they are
   the presentation layer, not the hot path.)  Host timing likewise
   goes through the recorder's host channel (``host_span`` /
   ``host_count``): ``jax.profiler``, ``TraceAnnotation`` and
   ``time.perf_counter`` / ``time.time`` under ``src/repro/serving/``
   outside ``telemetry.py`` are flagged, so every wall-clock read of the
   serving stack sits behind the one off-by-default switch.

Run:  python tools/lint_invariants.py          (from the repo root)
Exit: 0 clean, 1 with one ``path:line: message`` per violation.
"""
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

# topology kinds as compiled by TopologySpec.from_kind (core/topospec.py)
_TOPOLOGY_KINDS = ("homo", "two_pool", "fleetopt", "multipool", "semantic",
                   "semantic_fleetopt", "moe_pool", "moe_semantic",
                   "disagg", "disagg_fleetopt")
_KIND_DISPATCH = re.compile(
    r"""kind\s*(?:==|!=)\s*["'](?:%s)["']""" % "|".join(_TOPOLOGY_KINDS))
_KIND_ALLOWED = ("src/repro/core/topospec.py",
                 "tools/lint_invariants.py")   # this docstring's example

_MESH_API = re.compile(
    r"jax\.sharding\.(?:get_abstract_mesh|set_mesh|use_mesh|AxisType)\b"
    r"|from\s+jax\.sharding\s+import\s+[^\n]*"
    r"\b(?:get_abstract_mesh|set_mesh|use_mesh|AxisType)\b")
_MESH_ALLOWED = ("src/repro/models/compat.py",)

# bare print calls in the serving hot path (telemetry must ride the
# FleetScope recorder); `# lint: allow-print` opts a line out explicitly
_PRINT_CALL = re.compile(r"(?<![\w.])print\s*\(")
_PRINT_SCOPE = "src/repro/serving/"
_PRINT_OPT_OUT = "# lint: allow-print"

# host timing in the serving stack outside the recorder's host channel
_HOST_TIMING = re.compile(
    r"\bjax\.profiler\b|\bTraceAnnotation\b"
    r"|\btime\.(?:perf_counter|time)(?:_ns)?\b"
    r"|from\s+time\s+import\s+[^\n]*\b(?:perf_counter|time)(?:_ns)?\b")
_HOST_TIMING_ALLOWED = ("src/repro/serving/telemetry.py",)


def _scan(root: pathlib.Path = REPO) -> list:
    """All violations as (relpath, lineno, message) triples."""
    out = []
    for sub in ("src", "benchmarks", "examples", "tools"):
        base = root / sub
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            rel = path.relative_to(root).as_posix()
            text = path.read_text()
            for n, line in enumerate(text.splitlines(), 1):
                if _KIND_DISPATCH.search(line) and rel not in _KIND_ALLOWED:
                    out.append((rel, n,
                                "topology kind dispatch outside "
                                "TopologySpec.from_kind — consume the IR "
                                "(spec.pools / spec.router_policy) instead"))
                if _MESH_API.search(line) and rel not in _MESH_ALLOWED:
                    out.append((rel, n,
                                "jax.sharding mesh-context API outside "
                                "repro.models.compat — import the shim "
                                "from repro.models.compat instead"))
                if (rel.startswith(_PRINT_SCOPE)
                        and _PRINT_CALL.search(line)
                        and _PRINT_OPT_OUT not in line):
                    out.append((rel, n,
                                "print() in the serving hot path — emit "
                                "through serving.telemetry.TraceRecorder "
                                "(or tag '# lint: allow-print' if this "
                                "is genuinely presentation code)"))
                if (rel.startswith(_PRINT_SCOPE)
                        and rel not in _HOST_TIMING_ALLOWED
                        and _HOST_TIMING.search(line)):
                    out.append((rel, n,
                                "host timing in the serving stack outside "
                                "serving.telemetry — use host_span / "
                                "host_count, which stay off until "
                                "host_tracing() turns them on"))
    return out


def main() -> int:
    violations = _scan()
    for rel, n, msg in violations:
        print(f"{rel}:{n}: {msg}")
    if violations:
        print(f"\n{len(violations)} invariant violation(s)")
        return 1
    print("invariants clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
