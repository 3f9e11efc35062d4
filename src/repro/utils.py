"""Small shared utilities: pytree helpers, timing, deterministic rng streams,
and the persistent compile-cache switch the entry points call."""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import pathlib
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(levelname)s %(name)s] %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this, never an import.  A ``JAX_COMPILATION_CACHE_DIR``
    set by whoever runs the program is read by JAX itself and wins; otherwise
    the cache goes to a fixed ``<checkout>/.jax_cache``, since the directory
    must not move between runs for entries to be found again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_size_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (ShapeDtypeStruct or concrete)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def tree_num_params(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) for l in leaves if hasattr(l, "shape"))


def fold_rng(key: jax.Array, *names: str) -> jax.Array:
    """Derive a named sub-key deterministically from string names."""
    for name in names:
        key = jax.random.fold_in(key, abs(hash(name)) % (2**31))
    return key


@contextlib.contextmanager
def timed(label: str, sink: dict | None = None) -> Iterator[None]:
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    if sink is not None:
        sink[label] = dt
    logger.info("%s: %.3fs", label, dt)


def block_all(tree: Any) -> Any:
    """jax.block_until_ready on every leaf; returns the tree."""
    return jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x, tree
    )


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def asdict_shallow(dc: Any) -> dict:
    """dataclasses.asdict without deep-copying arrays."""
    return {f.name: getattr(dc, f.name) for f in dataclasses.fields(dc)}


def check_finite(tree: Any, where: str = "") -> None:
    """Host-side NaN/Inf check for tests and smoke runs."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f" and not np.all(np.isfinite(arr)):
            raise FloatingPointError(f"non-finite values at {where}{jax.tree_util.keystr(path)}")
