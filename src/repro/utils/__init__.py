from repro.utils.trees import (
    tree_bytes,
    tree_count,
    tree_flatten_with_names,
    tree_allclose,
    tree_zeros_like,
)

__all__ = [
    "tree_bytes",
    "tree_count",
    "tree_flatten_with_names",
    "tree_allclose",
    "tree_zeros_like",
]
