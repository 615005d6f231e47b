"""Plain PartPSP (Algorithm 2 of arXiv:2602.11544) over the Zamba2 reference.

The step of ``refs/partpsp.py`` (its layout, state, clip and DPPS round are
imported from there) around ``refs/zamba2.value_and_grad``: per node, one
after another, y_i = s_i / a_i; l_i <- l_i - gamma_l grad_l F_i(y_i, l_i);
g_i = grad_s F_i(y_i, l_i) at the updated l_i, clipped in L1 to C;
eps_i = -gamma_s g_i; then one DPPS round on s.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from benchlib.zamba2_params import leaf_shapes
from refs import zamba2
from refs.partpsp import (Layout, TrainState, _node_params, _perturbation,
                          _round, held_in)


def layout(model: dict, shared_rule: str) -> Layout:
    shapes = leaf_shapes(model)
    pat = re.compile(shared_rule)
    shared = tuple(p for p in shapes if pat.search(p))
    local = tuple(p for p in shapes if not pat.search(p))
    return Layout(shared, local, shapes)


def step(state: TrainState, tokens: jax.Array, key: jax.Array, *, cfg: dict,
         model: dict, lay: Layout, w: jax.Array, dtype=jnp.float32,
         mix_precision: str = "highest", half_batch: bool = False,
         gossip: bool = True):
    """One PartPSP step over all nodes; ``tokens`` (N, B, S).

    Returns (state, per-node losses, node-summed scaled noise of the round).
    The old local leaves are let go before the round, so a caller that
    hands over its only reference to ``state`` frees them there (at 4,096
    tokens the control's round does not fit next to them).
    ``dtype=bfloat16`` is the control (the model computes in bfloat16 and
    the parameters are stored in it); ``half_batch`` (each node reads only
    the first half of its tokens) and ``gossip=False`` plant faults.
    """
    pp, priv = cfg["partpsp"], cfg["privacy"]
    n = state.dpps.a.shape[0]
    t = int(state.dpps.t)
    k_noise = jax.random.split(jax.random.fold_in(key, t), 3)[2]
    if half_batch:
        tokens = tokens[:, :, : tokens.shape[2] // 2]
    losses, g_rows, local_new = [], [], {p: [] for p in lay.local}
    for i in range(n):
        y_i = state.dpps.s[i] / state.dpps.a[i]
        local_i = {p: state.local[p][i] for p in lay.local}
        loss_i, g1 = zamba2.value_and_grad(
            _node_params(lay, y_i, local_i), tokens[i], model=model,
            dtype=dtype, keep=lay.local)
        local_i = {p: local_i[p] - pp["gamma_l"] * g1[p] for p in lay.local}
        del g1
        _, g2 = zamba2.value_and_grad(
            _node_params(lay, y_i, local_i), tokens[i], model=model,
            dtype=dtype, keep=lay.shared)
        g_rows.append(lay.row(g2))
        del g2
        losses.append(loss_i)
        for p in lay.local:
            local_new[p].append(local_i[p])
    eps = _perturbation(g_rows, clip=pp["clip"], gamma_s=pp["gamma_s"])
    dstate = state.dpps
    del g_rows, state, local_i, y_i
    w_used = w if gossip else jnp.eye(n, dtype=jnp.float32)
    dstate, noise_sum = _round(dstate, eps, k_noise, w=w_used,
                               b=priv["b"], gamma_n=priv["gamma_n"],
                               c_prime=priv["c_prime"], lam=priv["lam"],
                               sync_interval=pp["sync_interval"],
                               precision=mix_precision)
    local = {p: jnp.stack(v) for p, v in local_new.items()}
    return (held_in(TrainState(dstate, local), dtype), jnp.stack(losses),
            noise_sum)
