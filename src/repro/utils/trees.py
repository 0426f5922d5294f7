"""Pytree utilities used across the framework."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


def tree_count(tree: Any) -> int:
    """Total number of array elements in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(l.shape) if hasattr(l, "shape") else 1 for l in leaves))


def tree_bytes(tree: Any) -> int:
    """Total bytes of a pytree (works on ShapeDtypeStruct too)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        else:
            total += 8
    return total


def _name_of_path(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def tree_flatten_with_names(tree: Any) -> list[tuple[str, Any]]:
    """Flatten a pytree into (slash/path/name, leaf) pairs, stable order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(_name_of_path(path), leaf) for path, leaf in flat]


def tree_allclose(a: Any, b: Any, rtol: float = 1e-5, atol: float = 1e-6) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return False
    return all(
        np.allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)
        for x, y in zip(la, lb)
    )


def tree_zeros_like(tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape, l.dtype), tree)


def tree_map_with_names(fn: Callable[[str, Any], Any], tree: Any) -> Any:
    """Map fn(name, leaf) over a pytree, preserving structure."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(_name_of_path(path), leaf), tree
    )


def place_like(tree: Any, like: Any) -> Any:
    """Put each leaf of the host ``tree`` on device with the sharding of
    the matching leaf of ``like`` (a restored state takes the layout of
    the live state it replaces, not the default device alone)."""
    return jax.tree_util.tree_map(
        lambda x, ref: jax.device_put(x, ref.sharding), tree, like)
