"""Sparse push-sum mixing block: padded-CSR SpMM for a (N, D) node block.

``out[i] = sum_k vals[i, k] * x[idx[i, k]]`` — the edge-list form of the
``pushsum_mix`` product, for the sparse gossip schedule
(``repro.core.pushsum.gossip_sparse``). Like ``pushsum_mix`` this is the
*within-host* path: N is small (the per-pod node count), so instead of a
vectorized gather the kernel expands the K CSR slots into the dense (N, N)
weight block in VMEM — one masked one-hot accumulation per slot, K is tiny
— and runs the same MXU-aligned (N, N) x (N, TILE_D) product per D-tile.
The expansion is O(K * N^2) VPU work on registers that the matmul reuses
across every D-tile's worth of flops; the HBM traffic drops from (N, N) to
the (N, K) edge list, which is what the sparse schedule is for.

Numerics: this block is validated against the jnp oracle
(``repro.kernels.ref.spmm``) to float tolerance, like every other kernel.
The conformance-grade bit-exactness pin (sparse == dense) lives on the
non-kernel path (``repro.core.pushsum.sparse_mix``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_D = 512


def _kernel(idx_ref, vals_ref, x_ref, o_ref):
    n, k = vals_ref.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    w = jnp.zeros((n, n), jnp.float32)
    for s in range(k):  # K is small and static: unrolled one-hot expansion
        sel = idx_ref[:, s][:, None] == cols
        w = w + jnp.where(sel, vals_ref[:, s][:, None], 0.0)
    o_ref[...] = jnp.dot(
        w, x_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # f32 on the MXU, not one bf16 pass
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm(idx: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray, *,
         interpret: bool) -> jnp.ndarray:
    """idx/vals: (N, K) padded CSR; x: (N, D), D a multiple of TILE_D."""
    n, d = x.shape
    assert idx.shape == vals.shape and idx.shape[0] == n, (idx.shape, x.shape)
    assert d % TILE_D == 0, d
    k = idx.shape[1]
    grid = (d // TILE_D,)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        interpret=interpret,
    )(idx.astype(jnp.int32), vals.astype(jnp.float32), x)
