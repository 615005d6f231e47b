"""Plain PartPSP (Algorithm 2 of arXiv:2602.11544) over the xLSTM reference.

Every node holds shared parameters (as its push-sum row s_i, weight a_i)
and local ones l_i. One step t, with k = fold_in(key, t) split into three
and the third part the round's noise key:

  y_i = s_i / a_i                                               (Eq. 10)
  l_i <- l_i - gamma_l grad_l F_i(y_i, l_i)                     (Eq. 5, 23)
  g_i = grad_s F_i(y_i, l_i)   at the updated l_i               (Eq. 6)
  g_i <- g_i / max(1, |g_i|_1 / C)                              (Eq. 24)
  eps_i = -gamma_s g_i                                          (Eq. 25)
  one DPPS round (refs/dpps.py) on s with eps                   (Alg. 1)

Nodes are computed one after another, so the reference fits next to
nothing else on one chip.
"""
from __future__ import annotations

import functools
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchlib.counts import size, xlstm_leaf_shapes
from benchlib.weights import nest
from refs import dpps, xlstm


class Layout(NamedTuple):
    shared: tuple[str, ...]   # shared paths in wire order
    local: tuple[str, ...]    # local paths in tree order
    shapes: dict              # path -> per-node shape

    def views(self, row: jax.Array) -> dict:
        out, off = {}, 0
        for p in self.shared:
            n = size(self.shapes[p])
            out[p] = row[off:off + n].reshape(self.shapes[p])
            off += n
        return out

    def row(self, leaves: dict) -> jax.Array:
        return jnp.concatenate([leaves[p].reshape(-1) for p in self.shared])


def layout(model: dict, shared_rule: str) -> Layout:
    shapes = xlstm_leaf_shapes(model)
    pat = re.compile(shared_rule)
    shared = tuple(p for p in shapes if pat.search(p))
    local = tuple(p for p in shapes if not pat.search(p))
    return Layout(shared, local, shapes)


class TrainState(NamedTuple):
    dpps: dpps.RefState
    local: dict           # path -> (N, ...) local leaves


def init(params: dict, lay: Layout) -> TrainState:
    """From node-stacked nested parameters (the benchmark's own)."""
    flat = flatten(params)
    n = flat[lay.shared[0]].shape[0]
    s0 = jnp.concatenate([flat[p].reshape(n, -1) for p in lay.shared], axis=1)
    return TrainState(dpps.init(s0), {p: flat[p] for p in lay.local})


def _node_params(lay: Layout, y_row, local_i):
    return nest({**lay.views(y_row), **local_i})


def step(state: TrainState, tokens: jax.Array, key: jax.Array, *, cfg: dict,
         lay: Layout, w: jax.Array, dtype=jnp.float32,
         mix_precision: str = "highest", half_batch: bool = False,
         gossip: bool = True):
    """One PartPSP step over all nodes.

    Returns (state, per-node losses, node-summed scaled noise of the
    round). ``dtype=bfloat16`` is the control: the model computes in
    bfloat16 and the parameters are stored in it. ``half_batch`` and
    ``gossip=False`` plant faults, for reading what a broken step does to
    the compared numbers.
    """
    model, pp, priv = cfg["model"], cfg["partpsp"], cfg["privacy"]
    n = state.dpps.a.shape[0]
    t = int(state.dpps.t)
    k_noise = jax.random.split(jax.random.fold_in(key, t), 3)[2]
    if half_batch:
        tokens = tokens[:, : tokens.shape[1] // 2]
    losses, g_rows, local_new = [], [], {p: [] for p in lay.local}
    for i in range(n):
        y_i = state.dpps.s[i] / state.dpps.a[i]
        local_i = {p: state.local[p][i] for p in lay.local}
        loss_i, g1 = xlstm.value_and_grad(
            _node_params(lay, y_i, local_i), tokens[i], model=model,
            dtype=dtype)
        g1 = flatten(g1)
        local_i = {p: local_i[p] - pp["gamma_l"] * g1[p] for p in lay.local}
        _, g2 = xlstm.value_and_grad(
            _node_params(lay, y_i, local_i), tokens[i], model=model,
            dtype=dtype)
        g_rows.append(lay.row(flatten(g2)))
        losses.append(loss_i)
        for p in lay.local:
            local_new[p].append(local_i[p])
    eps = _perturbation(g_rows, clip=pp["clip"], gamma_s=pp["gamma_s"])
    del g_rows
    w_used = w if gossip else jnp.eye(n, dtype=jnp.float32)
    dstate, noise_sum = _round(state.dpps, eps, k_noise, w=w_used,
                               b=priv["b"], gamma_n=priv["gamma_n"],
                               c_prime=priv["c_prime"], lam=priv["lam"],
                               sync_interval=pp["sync_interval"],
                               precision=mix_precision)
    local = {p: jnp.stack(v) for p, v in local_new.items()}
    return (held_in(TrainState(dstate, local), dtype), jnp.stack(losses),
            noise_sum)


def held_in(state: TrainState, dtype) -> TrainState:
    """The state as a program holding its parameters in ``dtype`` keeps it
    (the bfloat16 control rounds every stored parameter)."""
    if jnp.dtype(dtype) == jnp.float32:
        return state
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError(f"no stored-parameter control for {dtype}")
    rnd = dpps._bf16
    return TrainState(state.dpps._replace(s=rnd(state.dpps.s)),
                      {p: rnd(x) for p, x in state.local.items()})


@functools.partial(jax.jit, static_argnames=("clip", "gamma_s"))
def _perturbation(g_rows, *, clip: float, gamma_s: float):
    """eps_i = -gamma_s g_i / max(1, |g_i|_1 / C) over the stacked rows."""
    g = jnp.stack(g_rows)
    norms = jnp.sum(jnp.abs(g), axis=1)
    return -gamma_s * (g / jnp.maximum(1.0, norms / clip)[:, None])


_round = jax.jit(dpps.round_, static_argnames=(
    "b", "gamma_n", "c_prime", "lam", "sync_interval", "precision"))


def flatten(tree: dict, prefix: str = "") -> dict:
    """Nested parameter dict -> {path: leaf}."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out
