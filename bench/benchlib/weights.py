"""Weights and private values made on the device from the seed.

The benchmark makes every array the program and the reference consume, in
one jitted call each, so the reference never takes anything the program
made. The xLSTM initialiser follows the family's usual scheme: truncated
normal matrices scaled by 1/sqrt(fan_in), unit norm scales, and gate biases
that start the input gate small (-3) and the forget gate open (+3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchlib.counts import size, xlstm_leaf_shapes


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits (the driver's seeds
    do not fit 32)."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def nest(flat: dict[str, jax.Array]) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def _leaf_init(key, path: str, shape: tuple[int, ...], model: dict):
    d, h = model["d_model"], model["n_heads"]
    if path.endswith("ln/scale") or path == "final_ln/scale":
        return jnp.ones(shape, jnp.float32)
    if path.endswith("mlstm/cell/b_if"):
        gate = jnp.concatenate([jnp.full((h,), -3.0), jnp.full((h,), 3.0)])
        return jnp.broadcast_to(gate, shape).astype(jnp.float32)
    if path.endswith("slstm/cell/b"):
        gate = jnp.concatenate([jnp.zeros((d,)), jnp.full((d,), -3.0),
                                jnp.full((d,), 3.0), jnp.zeros((d,))])
        return jnp.broadcast_to(gate, shape).astype(jnp.float32)
    fan_in = shape[-2]
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            / jnp.sqrt(jnp.float32(fan_in)))


def _xlstm_flat(key, model: dict, paths: tuple[str, ...]):
    shapes = xlstm_leaf_shapes(model)
    return {p: _leaf_init(jax.random.fold_in(key, i), p, shapes[p], model)
            for i, p in enumerate(paths)}


def _model_key(model: dict) -> tuple:
    return tuple(sorted(model.items()))


@functools.lru_cache(maxsize=None)
def _xlstm_params_fn(model_items: tuple, n_nodes: int):
    model = dict(model_items)
    paths = tuple(xlstm_leaf_shapes(model))

    def make(key):
        flat = _xlstm_flat(key, model, paths)
        # Every node starts from the same parameters (a fresh buffer each).
        return nest({p: jnp.broadcast_to(x[None], (n_nodes,) + x.shape) + 0.0
                     for p, x in flat.items()})

    return jax.jit(make)


def xlstm_params(model: dict, key: jax.Array, n_nodes: int) -> dict:
    """Node-stacked initial parameters, identical on every node."""
    return _xlstm_params_fn(_model_key(model), n_nodes)(key)


@functools.lru_cache(maxsize=None)
def _normal_values_fn(shapes: tuple, n_nodes: int):
    d_s = sum(size(s) for s in shapes)

    def make(key):
        row = jax.random.normal(key, (n_nodes, d_s), jnp.float32)
        out, off = [], 0
        for s in shapes:
            out.append(row[:, off:off + size(s)].reshape((n_nodes,) + s))
            off += size(s)
        return out

    return jax.jit(make)


def normal_values(shapes: list, key: jax.Array, n_nodes: int):
    """Standard-normal private values over the given per-node leaf shapes."""
    return _normal_values_fn(tuple(tuple(s) for s in shapes), n_nodes)(key)
