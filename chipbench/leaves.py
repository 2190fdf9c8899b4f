"""Per-leaf norms of a parameter tree, for the training comparisons.

A leaf is one tensor of one layer: a leaf of the layer stack ``blocks``
(leading layer axis) counts once per layer, every other leaf once.  The
order is the tree's flatten order, layer by layer inside each stacked
leaf, so trees of the same structure give comparable vectors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.tree_util as jtu


def _stacked(path) -> bool:
    return bool(path) and getattr(path[0], "key", None) == "blocks"


def names(tree) -> list:
    out = []
    for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
        name = jtu.keystr(path)
        if _stacked(path):
            out += [f"{name}[{i}]" for i in range(leaf.shape[0])]
        else:
            out.append(name)
    return out


def norms(tree):
    """float32 vector of the L2 norm of every leaf (see module doc)."""
    parts = []
    for path, leaf in jtu.tree_flatten_with_path(tree)[0]:
        x = leaf.astype(jnp.float32)
        if _stacked(path):
            parts.append(jnp.sqrt(jnp.sum(jnp.square(x).reshape(
                x.shape[0], -1), axis=1)))
        else:
            parts.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(parts)


def diff_norms(a, b):
    """norms(a - b), leaf by leaf in float32."""
    return norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
