"""tools/lint_invariants.py: the repo itself must scan clean, and the
two rules must actually bite on violating code (a lint that never fires
is a green light taped over a hole)."""
import importlib.util
import os
import pathlib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "lint_invariants", os.path.join(ROOT, "tools", "lint_invariants.py"))
lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lint)


def test_repo_is_clean():
    assert lint._scan(pathlib.Path(ROOT)) == []


def _tree(tmp_path, rel, text):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return tmp_path


def test_kind_dispatch_outside_topospec_fires(tmp_path):
    root = _tree(tmp_path, "src/repro/serving/rogue.py",
                 'def f(kind):\n    if kind == "fleetopt":\n        pass\n')
    (rel, line, msg), = lint._scan(root)
    assert rel == "src/repro/serving/rogue.py" and line == 2
    assert "from_kind" in msg


def test_block_kind_literals_are_exempt(tmp_path):
    """b.kind == "attn" (repro.models) and shape.kind == "train"
    (repro.launch) are different enums — never flagged."""
    root = _tree(tmp_path, "src/repro/models/blocks.py",
                 'x = 1 if b.kind == "attn" else 2\n'
                 'y = 1 if shape.kind == "train" else 2\n')
    assert lint._scan(root) == []


def test_kind_dispatch_inside_topospec_allowed(tmp_path):
    root = _tree(tmp_path, "src/repro/core/topospec.py",
                 'if kind == "fleetopt":\n    pass\n')
    assert lint._scan(root) == []


def test_mesh_api_outside_compat_fires(tmp_path):
    root = _tree(tmp_path, "src/repro/launch/rogue.py",
                 "from jax.sharding import Mesh, set_mesh\n")
    (rel, _, msg), = lint._scan(root)
    assert rel == "src/repro/launch/rogue.py"
    assert "repro.models.compat" in msg
    # attribute-style access fires too
    root2 = _tree(tmp_path / "b", "src/x.py",
                  "m = jax.sharding.get_abstract_mesh()\n")
    assert len(lint._scan(root2)) == 1


def test_stable_sharding_names_are_fine(tmp_path):
    root = _tree(tmp_path, "src/repro/launch/ok.py",
                 "from jax.sharding import NamedSharding, PartitionSpec\n")
    assert lint._scan(root) == []


def test_importing_shims_from_compat_is_sanctioned(tmp_path):
    root = _tree(tmp_path, "src/repro/models/user.py",
                 "from repro.models.compat import set_mesh\n"
                 "from .compat import get_abstract_mesh\n")
    assert lint._scan(root) == []


def test_print_in_serving_hot_path_fires(tmp_path):
    root = _tree(tmp_path, "src/repro/serving/rogue.py",
                 'def step(self):\n    print("tick", self.t)\n')
    (rel, line, msg), = lint._scan(root)
    assert rel == "src/repro/serving/rogue.py" and line == 2
    assert "TraceRecorder" in msg


def test_print_outside_serving_and_opt_out_are_exempt(tmp_path):
    """Presentation layers print freely; a tagged serving line (e.g. a
    CLI entry point living next to the engines) opts out explicitly.
    Method names merely *ending* in print don't fire."""
    root = _tree(tmp_path, "benchmarks/report.py", 'print("| cell |")\n')
    _tree(root, "src/repro/serving/cli.py",
          'print("summary")  # lint: allow-print\n'
          "self.blueprint(x)\nfoo.print_tree()\n")
    assert lint._scan(root) == []


def test_host_timing_in_serving_outside_telemetry_fires(tmp_path):
    """Wall-clock reads and profiler annotations in the serving stack go
    through serving.telemetry's host channel, nowhere else."""
    root = _tree(tmp_path, "src/repro/serving/rogue.py",
                 "import time\n"
                 "t0 = time.perf_counter()\n"
                 "import jax.profiler\n"
                 "with jax.profiler.TraceAnnotation('x'):\n"
                 "    t1 = time.time_ns()\n"
                 "from time import perf_counter\n")
    got = lint._scan(root)
    assert [line for _, line, _ in got] == [2, 3, 4, 5, 6]
    assert all("host_span" in msg for _, _, msg in got)


def test_host_timing_in_telemetry_and_outside_serving_is_exempt(tmp_path):
    """The recorder itself reads the clock and opens the annotations;
    benchmarks time freely; `sim_time` and `time_s` names never fire."""
    root = _tree(tmp_path, "src/repro/serving/telemetry.py",
                 "from jax.profiler import TraceAnnotation\n"
                 "t = time.time_ns()\n")
    _tree(root, "benchmarks/bench.py", "t0 = time.perf_counter()\n")
    _tree(root, "src/repro/serving/engine.py",
          "self.sim_time.time = 1\nreq.ready_time = time_s\n")
    assert lint._scan(root) == []
