"""Push-sum mixing block: out = W @ s for a (N, D) node-stacked block.

N (the per-pod node count, 16-32) is tiny, so the mixing matmul is a skinny
(N, N) x (N, TILE_D) product per D-tile — MXU-aligned via the 128-lane tile.
On the production mesh the node dim is sharded and mixing happens through
collectives (see core/pushsum.py); this kernel is the *within-host* path
used when several logical nodes co-reside on one chip (benchmarks, tests,
and the single-host examples), replacing an HBM-bound einsum with a fused
VMEM-resident product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.laplace_noise import LANE

TILE_D = 512


def _kernel(w_ref, x_ref, o_ref):
    o_ref[...] = jnp.dot(
        w_ref[...], x_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # f32 on the MXU, not one bf16 pass
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pushsum_mix(w: jnp.ndarray, x: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """w: (N, N) f32; x: (N, D) with D a multiple of TILE_D (pad upstream)."""
    n, d = x.shape
    assert w.shape == (n, n)
    assert d % TILE_D == 0, d
    grid = (d // TILE_D,)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, TILE_D), lambda i: (0, i)),
        interpret=interpret,
    )(w.astype(jnp.float32), x)
