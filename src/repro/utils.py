"""Small shared utilities: pytree math, PRNG plumbing, dtype helpers."""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from pathlib import Path
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

#: the compile cache every entry point shares when the environment names
#: none: a fixed path, because the path is part of the cache key
REPO_JAX_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache is ``<repo>/.jax_cache``.
    Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
    ``repro.launch.*``) call this; tests and package imports do not.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_JAX_CACHE))
    return str(REPO_JAX_CACHE)


def tree_map(f: Callable, *trees: PyTree) -> PyTree:
    return jax.tree_util.tree_map(f, *trees)


def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return tree_map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def split_like(key: jax.Array, tree: PyTree) -> PyTree:
    """One PRNG key per leaf, arranged like ``tree``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(keys))


def fold_in_str(key: jax.Array, name: str) -> jax.Array:
    """Deterministically derive a key from a string tag."""
    h = 0
    for ch in name:
        h = (h * 131 + ord(ch)) % (2**31 - 1)
    return jax.random.fold_in(key, h)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, multiple: int) -> int:
    return ceil_div(a, multiple) * multiple


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PiB"


def human_flops(n: float) -> str:
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(n) < 1000:
            return f"{n:.2f} {unit}FLOP"
        n /= 1000
    return f"{n:.2f} EFLOP"


@dataclasses.dataclass
class Registry:
    """Name -> factory registry used for configs, baselines and kernels."""

    items: dict = dataclasses.field(default_factory=dict)

    def register(self, name: str):
        def deco(fn):
            if name in self.items:
                raise ValueError(f"duplicate registration: {name}")
            self.items[name] = fn
            return fn

        return deco

    def __getitem__(self, name: str):
        if name not in self.items:
            raise KeyError(f"unknown entry {name!r}; known: {sorted(self.items)}")
        return self.items[name]

    def names(self) -> list[str]:
        return sorted(self.items)


def chunk_iter(seq: Iterable, n: int):
    buf = []
    for item in seq:
        buf.append(item)
        if len(buf) == n:
            yield buf
            buf = []
    if buf:
        yield buf
