"""Pytree helpers for node-stacked parameter trees.

Throughout the core library, decentralized per-node state is represented as a
pytree whose every leaf carries a leading node dimension of size ``N``
(sharded over the mesh's gossip axes). These helpers compute per-node
reductions without flattening leaves together (flattening would destroy the
per-leaf "model"-axis shardings).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

__all__ = [
    "tree_l1_norm_per_node",
    "tree_l2_norm_sq_per_node",
    "tree_scale_per_node",
    "tree_add",
    "tree_sub",
    "tree_scale",
    "tree_zeros_like",
    "tree_node_mean",
    "tree_count_params",
    "tree_any_nan",
]


def _per_node_reduce(x: jnp.ndarray, fn) -> jnp.ndarray:
    """Reduce all non-leading axes of ``x`` -> shape (N,)."""
    axes = tuple(range(1, x.ndim))
    return fn(x, axes)


def tree_l1_norm_per_node(tree: PyTree) -> jnp.ndarray:
    """sum_leaves ||leaf_i||_1 for each node i -> (N,).

    Computed in *flat wire-row order*: every leaf flattens to (N, -1),
    the rows concatenate in leaf order, and one reduction sweeps the
    (N, d_s) row. This is the packed runtime's native layout
    (repro.core.packing stores exactly this row), so the packed path
    computes the identical reduction over its buffer slice with no
    per-leaf work — one reduce with one accumulation order on both paths
    is what keeps their norms bit-identical (summing per-leaf norms
    instead would pit two differently-fused reduction trees against each
    other, which XLA resolves ulp-differently per program).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    flats = [x.reshape(x.shape[0] if x.ndim else 1, -1) for x in leaves]
    row = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=1)
    return jnp.sum(jnp.abs(row), axis=1)


def tree_l2_norm_sq_per_node(tree: PyTree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    sq = [_per_node_reduce(jnp.square(x), jnp.sum) for x in leaves]
    return sum(sq[1:], start=sq[0])


def tree_scale_per_node(tree: PyTree, scale: jnp.ndarray) -> PyTree:
    """Multiply node i's slice of every leaf by scale[i]."""

    def mul(x):
        s = scale.reshape((-1,) + (1,) * (x.ndim - 1))
        return x * s.astype(x.dtype)

    return jax.tree_util.tree_map(mul, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(tree: PyTree, scale) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x * jnp.asarray(scale, x.dtype), tree)


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_node_mean(tree: PyTree) -> PyTree:
    """Average over the leading node dimension (the consensus target s-bar)."""
    return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), tree)


def tree_count_params(tree: PyTree, *, per_node: bool = True) -> int:
    """Total element count; with per_node=True the node dim is not counted."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = leaf.size
        if per_node and leaf.ndim >= 1:
            n //= leaf.shape[0]
        total += n
    return int(total)


def tree_any_nan(tree: PyTree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    flags = [jnp.any(~jnp.isfinite(x)) for x in leaves]
    out = flags[0]
    for f in flags[1:]:
        out = out | f
    return out
