"""Find a cell's files by name and build its objects.

A cell `<config>.<traffic>` of `BENCHMARK.json` reads
`bench/configs/<config>.json`, `bench/traffic/<traffic>.json` and the
pools the program sizes for it, `bench/sizing/<cell>.json`.  All three hold
data only.  Program objects are named by dotted path under the program's
package (`repro`); the plain reference (`bench.plainref`) reads the numbers
of the same entries:

    {"name": "model", "call": "core.modelspec.ModelSpec", "kwargs": {...}}
    {"name": "spec", "call": "core.topospec.TopologySpec.from_kind",
     "args": ["fleetopt", "$profile", "$model"], "kwargs": {"gamma": 2.0}}

A string argument `"$name"` is the object an earlier entry built.
"""
from __future__ import annotations

import importlib
import json
import pathlib
from typing import Any, Dict

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
PROGRAM_ROOT = "repro"


class CellError(ValueError):
    """A cell, configuration or traffic file that is missing or malformed."""


def _read(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise CellError(f"no such file: {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _read(ROOT / "BENCHMARK.json")


def workload_entry(name: str) -> dict:
    """The `workloads` entry of BENCHMARK.json called `name`."""
    for w in manifest()["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return _read(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return _read(BENCH / "traffic" / f"{name}.json")


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's manifest entry with its configuration and traffic."""
    entry = workload_entry(name)
    return dict(entry, config_data=load_config(entry["config"]),
                traffic_data=load_traffic(entry["traffic"]))


def resolve(root: str, dotted: str) -> Any:
    """`root.dotted`, importing the longest module prefix and taking the
    rest as attributes (`core.topospec.TopologySpec.from_kind`)."""
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join([root, *parts[:cut]]))
        except ModuleNotFoundError:
            continue
        for attr in parts[cut:]:
            obj = getattr(obj, attr)
        return obj
    raise CellError(f"cannot resolve {dotted!r} under {root!r}")


def _arg(value, built: Dict[str, Any]):
    if isinstance(value, str) and value.startswith("$"):
        return built[value[1:]]
    if isinstance(value, list):
        return tuple(_arg(v, built) for v in value)
    return value


def build(entries, root: str) -> Dict[str, Any]:
    """Evaluate a list of `{name, call, args, kwargs}` entries in order."""
    built: Dict[str, Any] = {}
    for e in entries:
        fn = resolve(root, e["call"])
        args = [_arg(a, built) for a in e.get("args", [])]
        kwargs = {k: _arg(v, built) for k, v in e.get("kwargs", {}).items()}
        built[e["name"]] = fn(*args, **kwargs)
    return built


def cell_objects(cell: Dict[str, Any], root: str = PROGRAM_ROOT
                 ) -> Dict[str, Any]:
    """The topology spec and the workload of a cell, built under `root`."""
    built = build(cell["config_data"]["build"], root)
    workload = build([cell["traffic_data"]["workload"]], root)["workload"]
    return dict(spec=built["spec"], workload=workload,
                prefill_chunk=cell["config_data"]["prefill_chunk"])
