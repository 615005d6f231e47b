"""Perturbed Push-Sum runtime (Nedic & Olshevsky; paper Alg. 1 lines 6-8).

State layout: every leaf of the gossiped pytree has a leading node dimension
``N``; the push-sum weights ``a`` are a ``(N,)`` vector. With the paper's
doubly-stochastic matrices (Def. 1) ``a`` provably stays at 1 (Eq. 16) — we
keep the full machinery anyway for faithfulness to Alg. 1 and assert the
invariant in property tests.

Two gossip schedules:

* ``gossip_dense`` — the literal matrix form ``s <- W s`` (paper maths).
  When the node dim is sharded over the mesh gossip axes, XLA lowers the
  contraction to an all-gather of the full shared tree: O(N * d_s) wire
  bytes per round. This is the paper-faithful baseline.
* ``gossip_circulant`` — both paper topologies (d-Out, EXP) are circulant,
  so mixing is a weighted sum of ``d`` rolls along the node axis, which XLA
  lowers to ``d-1`` collective-permutes: O(d * d_s) wire bytes. This is the
  beyond-paper optimized schedule (EXPERIMENTS.md SPerf #1).
* ``gossip_sparse`` — arbitrary sparse graphs (the net-lab families) as a
  padded-CSR edge list: gather the K in-neighbours per receiver and
  contract the slots, O(edges * d_s) per round instead of O(N^2 * d_s),
  bit-identical (f32) to ``gossip_dense`` on the same support
  (tests/test_sparse.py pins state and trajectory).

Within-host kernel routing: with ``use_kernels=True`` the dense schedule's
``W @ s`` runs through the MXU-shaped ``repro.kernels.pushsum_mix`` Pallas
block (one VMEM-resident product per leaf instead of an HBM-bound einsum),
as long as the (N, N) block fits VMEM; above that N the jnp path runs.
The circulant schedule has no kernel variant by design — its rolls are
permutations, pure data movement that XLA already lowers optimally (and to
collective-permutes when the node axis is sharded), so there is no MXU op
to fuse.

``gossip_packed`` is the packed-runtime hot path: the shared tree lives in
one ``(N, d_pad)`` buffer (see :mod:`repro.core.packing`) so dense mixing
is exactly one contraction per round, and the wire format becomes a single
cast — ``wire_dtype="bf16"`` mixes bf16 messages with fp32 accumulation
(the push-sum weights ``a`` always mix in fp32; the correction y = s/a
stays fp32).

Wire compression (repro.wire) deliberately does NOT live here: value
codecs (int8 stochastic rounding, top-k + error feedback) encode the
noised message in ``core.dpps.dpps_step`` — through
``PackedLayout.encode_wire``, strictly after noise injection — so every
gossip entry point in this module (dense, circulant, sparse, packed, and
the async mailbox's ``gossip_fn``) mixes the already-encoded f32 buffer
identically. The dequantized f32 view *is* the wire value; these mixers
never see, and never need to see, the codec.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.tree_utils import PyTree
from repro.obs.trace import PHASE_PUSHSUM_MIX, phase

__all__ = [
    "PushSumState",
    "init_push_sum",
    "gossip_dense",
    "gossip_circulant",
    "gossip_sparse",
    "gossip_packed",
    "gossip",
    "sparse_mix",
    "correct",
    "consensus_error",
]


class PushSumState(NamedTuple):
    s: PyTree          # gossiped values, leaves (N, ...)
    a: jnp.ndarray     # push-sum normalizing weights, (N,)

    @property
    def y(self) -> PyTree:
        return correct(self.s, self.a)


def init_push_sum(s: PyTree) -> PushSumState:
    leaves = jax.tree_util.tree_leaves(s)
    n = leaves[0].shape[0]
    return PushSumState(s=s, a=jnp.ones((n,), dtype=jnp.float32))


# Every mixing contraction runs at full f32 precision. On TPU an f32 dot
# otherwise defaults to a single bf16 pass, which breaks push-sum's f32
# mass conservation and stalls consensus near bf16 resolution; on CPU the
# setting changes nothing.
MIX_PRECISION = jax.lax.Precision.HIGHEST


def _mix_dense(w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    # out[i] = sum_j w[i, j] x[j]. Leaves with fewer than 3 trailing
    # columns — the (N,) push-sum weights especially — are zero-padded to
    # 3 columns and take the same gemm as everything else: XLA lowers
    # narrower contractions (gemv, d<3) to a lane-vectorized reduction
    # whose ordering depends on the contraction width, which the sparse
    # runtime cannot reproduce; at >= 3 output columns both paths share
    # the one sequential per-element reduction, keeping sparse == dense
    # bit-exact in f32 (tests/test_sparse.py pins it).
    d = 1
    for dim in x.shape[1:]:
        d *= dim
    if d < 3:
        n = x.shape[0]
        flat = x.reshape(n, d)
        padded = jnp.concatenate([flat, jnp.zeros((n, 3 - d), flat.dtype)],
                                 axis=1)
        out = jnp.einsum("ij,jd->id", w.astype(x.dtype), padded,
                         precision=MIX_PRECISION)
        return out[:, :d].reshape(x.shape)
    return jnp.einsum("ij,j...->i...", w.astype(x.dtype), x,
                      precision=MIX_PRECISION)


def sparse_mix(idx: jnp.ndarray, vals: jnp.ndarray,
               x: jnp.ndarray) -> jnp.ndarray:
    """Padded-CSR mix: ``out[i] = sum_k vals[i, k] * x[idx[i, k]]``.

    ``idx`` (B, K) int32 names the senders each receiver gathers, ascending
    per row with self-index zero-weight pads (``repro.core.topology
    .padded_csr``); ``vals`` (B, K) carries the weights. ``x`` may have
    more rows than ``idx`` (the sharded engine mixes a local row block
    against the all-gathered tree), so the output takes its leading dim
    from ``idx``.

    The contraction is one batched dot over the K slots, padded to >= 3
    trailing columns exactly like :func:`_mix_dense` — together with the
    ascending sender order this reproduces the dense gemm's reduction
    bit-for-bit in f32 (zero-weight pads are fma no-ops).
    """
    b, k = idx.shape
    g = x[idx]  # (B, K, ...)
    flat = g.reshape(b, k, -1)
    d = flat.shape[2]
    if d < 3:
        flat = jnp.concatenate(
            [flat, jnp.zeros((b, k, 3 - d), flat.dtype)], axis=2)
    out = jax.lax.dot_general(
        vals.astype(flat.dtype)[:, None, :], flat,
        (((2,), (1,)), ((0,), (0,))), precision=MIX_PRECISION)[:, 0]
    if d < 3:
        out = out[:, :d]
    return out.reshape((b,) + x.shape[1:])


def _mix_kernel_for(use_kernels: bool, n_nodes: int) -> bool:
    """Route an (N, N)-block mix to its Pallas kernel: when kernels are on
    and the block fits VMEM (``repro.kernels.ops.mix_block_fits``)."""
    if not use_kernels:
        return False
    from repro.kernels import ops as kops

    return kops.mix_block_fits(n_nodes)


def gossip_dense(state: PushSumState, w: jnp.ndarray, *,
                 use_kernels: bool = False) -> PushSumState:
    """One mixing round with an arbitrary (N, N) weight matrix.

    ``use_kernels=True`` routes every leaf's ``W @ s`` through the MXU
    block kernel ``repro.kernels.ops.pushsum_mix`` (Pallas on TPU,
    interpret-mode oracle elsewhere); the (N,) push-sum weights stay on
    the jnp matvec — too small to tile. The circulant schedule has no
    kernel counterpart (its rolls are permutations, not contractions);
    see :func:`gossip_circulant`.
    """
    with phase(PHASE_PUSHSUM_MIX):
        if _mix_kernel_for(use_kernels, w.shape[0]):
            from repro.kernels import ops as kops

            s_new = jax.tree_util.tree_map(lambda x: kops.pushsum_mix(w, x),
                                           state.s)
        else:
            s_new = jax.tree_util.tree_map(lambda x: _mix_dense(w, x),
                                           state.s)
        a_new = _mix_dense(w, state.a)
    return PushSumState(s=s_new, a=a_new)


def _mix_circulant(offsets: Sequence[int], weights: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    # Receiver i sums w_k * x[(i - k) mod N]: roll(+k) brings sender i-k to slot i.
    out = weights[0].astype(x.dtype) * x if offsets[0] == 0 else (
        weights[0].astype(x.dtype) * jnp.roll(x, offsets[0], axis=0))
    for k, off in enumerate(offsets[1:], start=1):
        out = out + weights[k].astype(x.dtype) * jnp.roll(x, off, axis=0)
    return out


def gossip_circulant(
    state: PushSumState, offsets: Sequence[int], weights: jnp.ndarray
) -> PushSumState:
    """One mixing round for a circulant topology.

    ``offsets`` must be static ints (they pick the permutation); ``weights``
    may be traced. ``jnp.roll`` along the node-sharded axis lowers to a
    collective-permute, giving the cheap schedule described above.
    """
    offsets = tuple(int(o) for o in offsets)
    with phase(PHASE_PUSHSUM_MIX):
        s_new = jax.tree_util.tree_map(
            lambda x: _mix_circulant(offsets, weights, x), state.s
        )
        a_new = _mix_circulant(offsets, weights, state.a)
    return PushSumState(s=s_new, a=a_new)


def gossip_sparse(
    state: PushSumState, idx: jnp.ndarray, vals: jnp.ndarray, *,
    use_kernels: bool = False,
) -> PushSumState:
    """One mixing round over a padded-CSR edge list (idx, vals).

    The sparse twin of :func:`gossip_dense`: per-round cost is O(edges *
    d_s) instead of O(N^2 * d_s), and on the topology's own CSR export the
    result is bit-identical (f32) to the dense mix (tests/test_sparse.py).
    ``use_kernels=True`` routes each leaf through the Pallas SpMM block
    ``repro.kernels.ops.pushsum_mix_sparse``; the (N,) push-sum weights
    stay on the jnp path — too small to tile.
    """
    with phase(PHASE_PUSHSUM_MIX):
        if _mix_kernel_for(use_kernels, idx.shape[0]):
            from repro.kernels import ops as kops

            s_new = jax.tree_util.tree_map(
                lambda x: kops.pushsum_mix_sparse(idx, vals, x), state.s)
        else:
            s_new = jax.tree_util.tree_map(
                lambda x: sparse_mix(idx, vals, x), state.s)
        a_new = sparse_mix(idx, vals, state.a)
    return PushSumState(s=s_new, a=a_new)


def gossip_packed(
    state: PushSumState,
    *,
    w: jnp.ndarray | None = None,
    offsets: Sequence[int] | None = None,
    weights: jnp.ndarray | None = None,
    sparse_idx: jnp.ndarray | None = None,
    sparse_vals: jnp.ndarray | None = None,
    wire_dtype: str = "f32",
    use_kernels: bool = False,
) -> PushSumState:
    """Eq. 9 over the packed (N, d_pad) buffer — one mix op per round.

    ``state.s`` is the single contiguous buffer of
    :class:`repro.core.packing.PackedLayout`, not a pytree. In fp32 wire
    mode every op is the same op the pytree path applies per leaf, so the
    result is bit-identical to the oracle (tests/test_engine.py pins it).
    ``wire_dtype="bf16"`` casts the outgoing messages once (the packed
    layout makes the wire format a single cast), mixes them with fp32
    accumulation, and returns fp32; the push-sum weights ``a`` always mix
    in fp32. Dense + ``use_kernels`` routes the contraction through the
    MXU ``pushsum_mix`` block.
    """
    buf = state.s
    bf16 = wire_dtype == "bf16"
    with phase(PHASE_PUSHSUM_MIX):
        wire = buf.astype(jnp.bfloat16) if bf16 else buf
        if offsets is not None:
            offsets = tuple(int(o) for o in offsets)
            if weights is None:
                weights = jnp.full((len(offsets),), 1.0 / len(offsets),
                                   jnp.float32)
            if bf16:
                # accumulate in fp32: each rolled bf16 message is upcast
                # before the weighted sum (the cast is the wire round-trip).
                acc = weights[0] * (wire if offsets[0] == 0 else
                                    jnp.roll(wire, offsets[0], axis=0)
                                    ).astype(jnp.float32)
                for k, off in enumerate(offsets[1:], start=1):
                    acc = acc + weights[k] * jnp.roll(
                        wire, off, axis=0).astype(jnp.float32)
                s_new = acc
            else:
                s_new = _mix_circulant(offsets, weights, wire)
            a_new = _mix_circulant(offsets, weights, state.a)
            return PushSumState(s=s_new, a=a_new)
        if sparse_idx is not None:
            if bf16:
                # Mirror the dense bf16 contract: bf16 messages, fp32
                # accumulation, fp32 result (no kernel for the same reason
                # as the dense branch below).
                g = wire[sparse_idx]  # (N, K, d_pad) bf16
                s_new = jnp.einsum("nk,nkd->nd", sparse_vals, g,
                                   preferred_element_type=jnp.float32)
            elif _mix_kernel_for(use_kernels, sparse_idx.shape[0]):
                from repro.kernels import ops as kops

                s_new = kops.pushsum_mix_sparse(sparse_idx, sparse_vals,
                                                wire)
            else:
                s_new = sparse_mix(sparse_idx, sparse_vals, wire)
            a_new = sparse_mix(sparse_idx, sparse_vals, state.a)
            return PushSumState(s=s_new, a=a_new)
        if w is None:
            raise ValueError(
                "gossip_packed() needs w=, offsets=, or "
                "sparse_idx=/sparse_vals=")
        if bf16:
            # Always the einsum here, even under use_kernels: the
            # pushsum_mix kernel writes its accumulator back in the wire
            # dtype, which would re-quantize the mixed state to bf16 every
            # round — the wire format's contract is bf16 messages with an
            # fp32 result.
            s_new = jnp.einsum("ij,jd->id", w, wire,
                               preferred_element_type=jnp.float32)
        elif _mix_kernel_for(use_kernels, w.shape[0]):
            from repro.kernels import ops as kops

            s_new = kops.pushsum_mix(w, wire)
        else:
            s_new = _mix_dense(w, wire)
        a_new = _mix_dense(w, state.a)
    return PushSumState(s=s_new, a=a_new)


def gossip(
    state: PushSumState,
    *,
    w: jnp.ndarray | None = None,
    offsets: Sequence[int] | None = None,
    weights: jnp.ndarray | None = None,
) -> PushSumState:
    """Dispatch on the supplied schedule (dense matrix vs circulant offsets)."""
    if offsets is not None:
        if weights is None:
            weights = jnp.full((len(offsets),), 1.0 / len(offsets), jnp.float32)
        return gossip_circulant(state, offsets, weights)
    if w is None:
        raise ValueError("gossip() needs either w= or offsets=")
    return gossip_dense(state, w)


def correct(s: PyTree, a: jnp.ndarray) -> PyTree:
    """Push-sum correction y_i = s_i / a_i (paper Eq. 10)."""

    def div(x):
        denom = a.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return x / denom

    return jax.tree_util.tree_map(div, s)


def consensus_error(s: PyTree) -> jnp.ndarray:
    """max_i sum_leaves ||s_i - s_bar||_1 — how far from consensus the net is."""
    from repro.core.tree_utils import tree_l1_norm_per_node, tree_node_mean

    mean = tree_node_mean(s)
    diff = jax.tree_util.tree_map(lambda x, m: x - m[None], s, mean)
    return jnp.max(tree_l1_norm_per_node(diff))
