"""The one home of JAX APIs whose spelling moves between releases.

Everything in models/, launch/ and serving/ that needs the ambient mesh, a
cost analysis, a scoped 64-bit mode or the persistent compilation cache
goes through this module, so a JAX upgrade touches one file
(`tools/lint_invariants.py` enforces the mesh part).  The installed
release is JAX 0.9.
"""
from __future__ import annotations

import contextlib
import os
import pathlib
from typing import Any, Sequence

import jax

# fixed cache path inside the checkout (gitignored): the directory is part
# of the cache key, so it must not move between runs
DEFAULT_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                     / "benchmarks" / "results" / ".xla_cache")


def get_abstract_mesh() -> Any:
    """The ambient mesh (an `AbstractMesh`, possibly empty)."""
    return jax.sharding.get_abstract_mesh()


def set_mesh(mesh) -> contextlib.AbstractContextManager:
    """Context manager installing `mesh` as the ambient mesh."""
    return jax.sharding.set_mesh(mesh)


def cost_analysis(compiled) -> dict:
    """Flat cost dict from a compiled executable."""
    return compiled.cost_analysis() or {}


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              **kwargs):
    """`jax.make_mesh` with Auto axis types."""
    kwargs.setdefault("axis_types",
                      (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def enable_x64() -> contextlib.AbstractContextManager:
    """Context manager turning on 64-bit types for the block it wraps
    (the fleet drain's float64 meters); the process default stays 32-bit."""
    return jax.enable_x64(True)


def enable_compile_cache() -> pathlib.Path:
    """Turn on JAX's persistent compilation cache before the first compile.

    With `JAX_COMPILATION_CACHE_DIR` set, JAX already reads that directory
    and no other is set here; otherwise the cache lives at the fixed
    `DEFAULT_CACHE_DIR`.  Every program is cached, however fast it
    compiled.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = pathlib.Path(env) if env else DEFAULT_CACHE_DIR
    if not env:
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
