"""Shared small utilities: pytree helpers, initializers, rng plumbing."""
from __future__ import annotations

import math
import os
from pathlib import Path
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def enable_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key,
    so it must not move between runs.  Returns the directory in use.
    Entry points call this from ``main``; tests never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Initializers (functional; every init takes an explicit key).
# ---------------------------------------------------------------------------

def normal_init(key, shape, dtype=jnp.float32, stddev=0.02):
    return (jax.random.normal(key, shape) * stddev).astype(dtype)


def lecun_init(key, shape, dtype=jnp.float32, fan_in_axes=(0,)):
    fan_in = int(np.prod([shape[a] for a in fan_in_axes]))
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def zeros_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.zeros(shape, dtype)


def ones_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.ones(shape, dtype)


def split_keys(key, names: Iterable[str]) -> dict[str, jax.Array]:
    names = list(names)
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------

def tree_size(tree: PyTree) -> int:
    """Total number of scalar parameters in a pytree (works on SDS too)."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_paths(tree: PyTree) -> list[tuple[str, Any]]:
    """Flatten to ('a/b/c', leaf) pairs using dict keys as path parts."""
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        parts = []
        for p in path:
            if isinstance(p, jax.tree_util.DictKey):
                parts.append(str(p.key))
            elif isinstance(p, jax.tree_util.SequenceKey):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        out.append(("/".join(parts), leaf))
    return out


def tree_map_with_path(fn: Callable[[str, Any], Any], tree: PyTree) -> PyTree:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for path, leaf in flat:
        parts = []
        for p in path:
            if isinstance(p, jax.tree_util.DictKey):
                parts.append(str(p.key))
            elif isinstance(p, jax.tree_util.SequenceKey):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        leaves.append(fn("/".join(parts), leaf))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def abstract_init(init_fn: Callable[..., PyTree], *args) -> PyTree:
    """Shape-only init: returns a pytree of ShapeDtypeStruct, no allocation."""
    return jax.eval_shape(init_fn, *args)


def cast_tree(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def softcap(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
