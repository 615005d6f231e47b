"""Sharded protocol execution: the node axis on a real device mesh.

``repro.engine.rounds`` compiles the multi-round protocol into one program;
this module places the node dimension of that program onto the mesh's gossip
axis via ``shard_map`` and lowers each gossip schedule to its natural
collective:

* circulant — each static offset k becomes a global roll of the block-
  sharded node axis: whole-block ``lax.ppermute``s plus one boundary
  exchange (O(d * d_s) wire bytes per round, d = union out-degree). This is
  the cheap schedule (EXPERIMENTS.md SPerf #1).
* dense     — the paper-faithful baseline: ``lax.all_gather`` of the full
  shared tree followed by the local rows of the W contraction
  (O(N * d_s) wire bytes per round).
* sparse    — all-gather the shared tree exactly like dense, then mix only
  the local receivers' padded-CSR rows (``repro.core.pushsum.sparse_mix``
  against the gathered tree): same wire bytes as dense but O(edges/shards
  * d_s) local flops. Static sparse plans only — fault-masked plans
  (``ProtocolPlan.dynamic``) stay on the single-device engine (see
  :func:`_check_cfg`).

Node-axis reductions (the sensitivity max of Alg. 1 line 4, sync averaging,
metric aggregation) become ``lax.pmax`` / ``lax.pmean`` over the gossip axis
through the :class:`repro.core.dpps.NodeOps` seam, so every scalar metric
leaves the shard_map already replicated.

Noise keys are folded with ``lax.axis_index`` so shards draw independent
Laplace noise (the DP guarantee needs independent per-node noise; the draw
is therefore *not* bit-identical to the single-device engine — noiseless
runs are, which is what tests pin).

The packed runtime (``ProtocolPlan.packed``, the default) needs no special
handling here: ``repro.engine.rounds`` packs *inside* the shard_map body,
so each shard flattens its local ``(N/shards, ...)`` block into its own
``(N/shards, d_pad)`` buffer and the node axis shards exactly as before —
the in/out specs below are written against the caller-visible pytree
state. Dense gossip then all-gathers one contiguous buffer per round
instead of one tensor per leaf. ``wire_dtype="bf16"`` is not implemented
for the collective gossip path (dpps_step raises; use f32 on the mesh),
and wire codecs (``ProtocolPlan.wire``, repro.wire) are rejected the same
way (:func:`_check_cfg`).

Scope: one gossip axis (single-pod meshes — axis "data"). Multi-pod meshes
(two gossip axes) currently go through the auto-sharded ``jax.jit`` path in
``launch/steps.py``; collapsing ("pod", "data") into one logical axis here
is future work. ``sensitivity_mode="real"`` is unsupported (it needs the
O(N^2) pairwise distances across shards) — it is an experiments-only mode.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.dpps import DPPSConfig, DPPSState, NodeOps
from repro.core.partpsp import PartPSPConfig, PartPSPState
from repro.core.pushsum import MIX_PRECISION, PushSumState, sparse_mix
from repro.core.sensitivity import SensitivityState
from repro.engine import rounds as _rounds
from repro.engine.plan import ProtocolPlan
from repro.launch.mesh import gossip_axes

__all__ = [
    "sharded_node_ops",
    "sharded_gossip_builder",
    "shard_run_dpps",
    "shard_run_partpsp",
]

# Per-node metric trajectories are dropped under sharding (scalar metrics are
# pmax/pmean-reduced and replicated; per-node series would force ragged
# out_specs for little diagnostic value on a fleet). Transcript-tap series
# (repro.audit) are per-node wire recordings and are dropped the same way —
# the audit lab runs on the single-device engine by design.
_PER_NODE_METRICS = ("sensitivity_local", "loss_per_node")


def _drop_unsharded(traj: dict[str, Any]) -> dict[str, Any]:
    for name in _PER_NODE_METRICS:
        traj.pop(name, None)
    for name in [k for k in traj if k.startswith("tap_")]:
        traj.pop(name)
    return traj


def _gossip_axis(mesh) -> tuple[str, int]:
    axes = gossip_axes(mesh)
    if len(axes) != 1:
        raise NotImplementedError(
            f"sharded engine supports one gossip axis, mesh has {axes}; "
            "use the auto-sharded jit path (launch/steps.py) for multi-pod")
    name = axes[0]
    return name, int(mesh.shape[name])


def sharded_node_ops(axis_name: str) -> NodeOps:
    """NodeOps whose reductions span the sharded node axis."""
    return NodeOps(
        vmax=lambda x: lax.pmax(jnp.max(x), axis_name),
        vmin=lambda x: lax.pmin(jnp.min(x), axis_name),
        vmean=lambda x: lax.pmean(jnp.mean(x), axis_name),
        leaf_mean=lambda x: lax.pmean(
            jnp.mean(x, axis=0, keepdims=True), axis_name),
    )


def _sharded_roll(x: jnp.ndarray, shift: int, axis_name: str,
                  n_shards: int) -> jnp.ndarray:
    """Global roll by static ``shift`` of a block-sharded leading axis.

    Device d holds rows [d*L, (d+1)*L). Decompose shift = q*L + r: the bulk
    is a whole-block ppermute by q, the remainder r a boundary exchange with
    the next block over.
    """
    block = x.shape[0]
    q, r = divmod(shift % (block * n_shards), block)
    perm_q = [(s, (s + q) % n_shards) for s in range(n_shards)]
    bulk = lax.ppermute(x, axis_name, perm_q) if q else x
    if r == 0:
        return bulk
    prev = lax.ppermute(x, axis_name,
                        [(s, (s + q + 1) % n_shards) for s in range(n_shards)])
    return jnp.concatenate([prev[block - r:], bulk[:block - r]], axis=0)


def sharded_gossip_builder(plan: ProtocolPlan, axis_name: str, n_shards: int):
    """Per-round gossip_fn factory for the shard_map'd scan body.

    Receives the round's mixing operands (``plan.mix_at(t)`` output) and
    returns the collective mixing closure ``dpps_step`` plugs in at Eq. 9.
    """
    if plan.schedule == "circulant":
        offsets = plan.offsets

        def builder(mix):
            wts = mix["mix_weights"]

            def mix_leaf(x):
                out = wts[0].astype(x.dtype) * (
                    x if offsets[0] == 0
                    else _sharded_roll(x, offsets[0], axis_name, n_shards))
                for k, off in enumerate(offsets[1:], start=1):
                    out = out + wts[k].astype(x.dtype) * _sharded_roll(
                        x, off, axis_name, n_shards)
                return out

            def gossip_fn(push: PushSumState) -> PushSumState:
                s_new = jax.tree_util.tree_map(mix_leaf, push.s)
                return PushSumState(s=s_new, a=mix_leaf(push.a))

            return gossip_fn

        return builder

    if plan.schedule == "sparse":

        def builder(mix):
            idx = mix["sparse_idx"]    # (N, K), replicated
            vals = mix["sparse_vals"]  # (N, K), replicated

            def mix_leaf(x):
                full = lax.all_gather(x, axis_name, axis=0, tiled=True)
                block = x.shape[0]
                row0 = lax.axis_index(axis_name) * block
                idx_rows = lax.dynamic_slice_in_dim(idx, row0, block, axis=0)
                vals_rows = lax.dynamic_slice_in_dim(vals, row0, block, axis=0)
                return sparse_mix(idx_rows, vals_rows, full)

            def gossip_fn(push: PushSumState) -> PushSumState:
                s_new = jax.tree_util.tree_map(mix_leaf, push.s)
                return PushSumState(s=s_new, a=mix_leaf(push.a))

            return gossip_fn

        return builder

    def builder(mix):
        w = mix["w"]  # (N, N), replicated

        def mix_leaf(x):
            full = lax.all_gather(x, axis_name, axis=0, tiled=True)  # (N, ...)
            block = x.shape[0]
            row0 = lax.axis_index(axis_name) * block
            w_rows = lax.dynamic_slice_in_dim(w, row0, block, axis=0)
            return jnp.einsum("ij,j...->i...", w_rows.astype(x.dtype), full,
                              precision=MIX_PRECISION)

        def gossip_fn(push: PushSumState) -> PushSumState:
            s_new = jax.tree_util.tree_map(mix_leaf, push.s)
            return PushSumState(s=s_new, a=mix_leaf(push.a))

        return gossip_fn

    return builder


def _node_spec(axis_name: str):
    return lambda x: P(axis_name, *((None,) * (x.ndim - 1)))


def _dpps_state_specs(state: DPPSState, axis_name: str) -> DPPSState:
    node = _node_spec(axis_name)
    return DPPSState(
        push=PushSumState(
            s=jax.tree_util.tree_map(node, state.push.s),
            a=P(axis_name)),
        sens=SensitivityState(
            s_local=P(axis_name), prev_noise_l1=P(axis_name),
            c_prime=P(), lam=P()),
        t=P(),
    )


def _partpsp_state_specs(state: PartPSPState, axis_name: str) -> PartPSPState:
    node = _node_spec(axis_name)
    return PartPSPState(
        dpps=_dpps_state_specs(state.dpps, axis_name),
        local=jax.tree_util.tree_map(node, state.local),
    )


def _seq_spec(axis_name: str):
    """(T, N, ...) scan inputs: round axis replicated, node axis sharded."""
    return lambda x: P(None, axis_name, *((None,) * (x.ndim - 2)))


def _check_cfg(cfg: DPPSConfig, n_nodes: int, n_shards: int,
               plan: ProtocolPlan | None = None) -> None:
    if cfg.sensitivity_mode == "real":
        raise ValueError("sensitivity_mode='real' is experiments-only and "
                         "unsupported under sharding")
    if n_nodes % n_shards != 0:
        raise ValueError(f"node count {n_nodes} must divide evenly over "
                         f"{n_shards} gossip shards")
    if plan is not None and getattr(plan, "dynamic", False):
        raise NotImplementedError(
            "fault injection (ProtocolPlan.dynamic / faults=) is not "
            "implemented for the sharded engine: per-round masking and "
            "column renormalization need a global view of each sender's "
            "surviving mass, which the collective gossip path never "
            "materializes. Run fault studies on the single-device engine — "
            "schedule='sparse' masks the edge list there without ever "
            "stacking dense (T, N, N) weights; *static* sparse plans (no "
            "faults) shard fine.")
    codec = None if plan is None else getattr(plan, "wire", None)
    if codec is not None:
        raise NotImplementedError(
            f"wire codec {codec.name!r} (ProtocolPlan.wire / wire=) is not "
            "implemented for the sharded engine: the codec's per-node "
            "encode (and its error-feedback residual) runs on the packed "
            "(N, d_s) buffer, which the shard_map body builds per shard "
            "while the all-gathered gossip operand crosses shards "
            "unencoded. Run wire-compression studies on the "
            "single-device engine.")


def shard_run_dpps(
    mesh,
    state: DPPSState,
    eps_seq,
    key: jax.Array,
    *,
    cfg: DPPSConfig,
    plan: ProtocolPlan,
    rounds: int | None = None,
) -> tuple[DPPSState, dict[str, jnp.ndarray]]:
    """:func:`repro.engine.rounds.run_dpps`, node axis sharded over ``mesh``."""
    axis_name, n_shards = _gossip_axis(mesh)
    _check_cfg(plan.resolve_dpps(cfg), state.push.a.shape[0], n_shards, plan)
    if eps_seq is None:
        if rounds is None:
            raise ValueError("rounds= is required when eps_seq is None")
        # Materialize the zero perturbations so the scan inputs (and their
        # shard specs) have the uniform (T, N, ...) layout.
        eps_seq = jax.tree_util.tree_map(
            lambda x: jnp.zeros((rounds,) + x.shape, x.dtype), state.push.s)

    inner = functools.partial(
        _rounds.run_dpps, cfg=cfg, plan=plan,
        _gossip_builder=sharded_gossip_builder(plan, axis_name, n_shards),
        _node_ops=sharded_node_ops(axis_name),
        _key_fold=lambda k: jax.random.fold_in(k, lax.axis_index(axis_name)))

    def fn(state, eps_seq, key):
        final, traj = inner(state, eps_seq, key)
        return final, _drop_unsharded(traj)

    state_specs = _dpps_state_specs(state, axis_name)
    eps_specs = jax.tree_util.tree_map(_seq_spec(axis_name), eps_seq)
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(state_specs, eps_specs, P()),
        out_specs=(state_specs, P(None)),
        check_vma=False)
    return sharded(state, eps_seq, key)


def shard_run_partpsp(
    mesh,
    state: PartPSPState,
    batches,
    key: jax.Array,
    *,
    cfg: PartPSPConfig,
    partition,
    loss_fn,
    plan: ProtocolPlan,
) -> tuple[PartPSPState, dict[str, jnp.ndarray]]:
    """:func:`repro.engine.rounds.run_partpsp` under shard_map.

    ``batches`` leaves are (T, N, per_node, ...): the node axis (dim 1)
    shards over the gossip axis, rounds stay the scan axis.
    """
    axis_name, n_shards = _gossip_axis(mesh)
    _check_cfg(plan.resolve_dpps(cfg.dpps), state.dpps.push.a.shape[0],
               n_shards, plan)

    inner = functools.partial(
        _rounds.run_partpsp, cfg=cfg, partition=partition, loss_fn=loss_fn,
        plan=plan,
        _gossip_builder=sharded_gossip_builder(plan, axis_name, n_shards),
        _node_ops=sharded_node_ops(axis_name),
        _key_fold=lambda k: jax.random.fold_in(k, lax.axis_index(axis_name)))

    def fn(state, batches, key):
        final, traj = inner(state, batches, key)
        return final, _drop_unsharded(traj)

    state_specs = _partpsp_state_specs(state, axis_name)
    batch_specs = jax.tree_util.tree_map(_seq_spec(axis_name), batches)
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(state_specs, batch_specs, P()),
        out_specs=(state_specs, P(None)),
        check_vma=False)
    return sharded(state, batches, key)
