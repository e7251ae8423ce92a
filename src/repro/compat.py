"""The repo's few seams onto the installed JAX (0.9).

Everything else calls JAX by its own names (``jax.shard_map``,
``jax.sharding.AbstractMesh``, ``pltpu.CompilerParams``).  What lives here
holds a decision more than one module depends on:

* :func:`make_mesh` — every mesh the repo builds has ``Auto`` axes
  (``jax.make_mesh`` defaults to ``Explicit``);
* :func:`vary_like` — ``shard_map``'s varying-manual-axes typing for
  carries the engines build from replicated values;
* :func:`pallas_interpret_default` — Pallas runs interpreted on the CPU
  backend and compiled everywhere else;
* :func:`enable_compile_cache` — the persistent compilation cache every
  entry point shares.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache: a fixed path inside the checkout (the cache key
# includes it, so a path that moved between runs would never hit)
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         devices=devices)


def vary_like(tree, *refs):
    """Mark every leaf of ``tree`` as varying over the manual mesh axes
    that any of ``refs`` varies over.

    Inside ``shard_map`` a ``lax.scan`` carry or ``lax.cond`` operand that
    the body builds from replicated values (a fresh ``PlatformState``, a
    constant) is typed invariant, while the value the body returns for it
    varies with the sharded inputs; JAX rejects the mismatch.  Casting the
    initial value to the refs' axes fixes the type without moving data.
    Outside ``shard_map`` nothing varies and this is the identity, so the
    single-device and sharded engines share one body."""
    want = frozenset().union(*(jax.typeof(r).vma for r in refs))

    def cast(x):
        missing = tuple(sorted(want - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree_util.tree_map(cast, tree) if want else tree


def _interpret_for(platform: str) -> bool:
    """Pallas runs interpreted exactly when the backend is the CPU, which
    has no Pallas compiler; any other backend compiles the kernel."""
    return platform == "cpu"


def pallas_interpret_default() -> bool:
    """Default for every kernel's ``interpret=`` argument."""
    return _interpret_for(jax.devices()[0].platform)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives in
    :data:`COMPILE_CACHE_DIR`.  Tests do not call this."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
