"""L1-norm reduce + clip-scale kernels (paper Eq. 24).

Two tiled passes: (1) per-tile |x| partial sums -> one scalar sum outside
the kernel, (2) x / max(1, norm/C) applied tile-wise. The reduction emits
one lane-aligned (8, 128) block of partials per grid step — cheap,
deterministic, and free of cross-step output aliasing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.laplace_noise import (
    LANE,
    LANE_ROWS,
    SMEM_SPEC,
    TILE_ROWS,
    fold_rows,
)


def _norm_kernel(x_ref, o_ref):
    o_ref[...] = fold_rows(jnp.abs(x_ref[...].astype(jnp.float32)))


def _scale_kernel(x_ref, denom_ref, o_ref):
    o_ref[...] = (x_ref[...].astype(jnp.float32) / denom_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def l1_norm(x: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    r, lane = x.shape
    assert lane == LANE and r % TILE_ROWS == 0, (r, lane)
    grid = (r // TILE_ROWS,)
    partials = pl.pallas_call(
        _norm_kernel,
        out_shape=jax.ShapeDtypeStruct((grid[0] * LANE_ROWS, LANE),
                                       jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((LANE_ROWS, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(x)
    return jnp.sum(partials)


@functools.partial(jax.jit, static_argnames=("interpret",))
def clip_scale(x: jnp.ndarray, denom: jnp.ndarray, *,
               interpret: bool) -> jnp.ndarray:
    """x / denom, tile-wise (denom precomputed as max(1, norm/C))."""
    r, lane = x.shape
    assert lane == LANE and r % TILE_ROWS == 0, (r, lane)
    grid = (r // TILE_ROWS,)
    return pl.pallas_call(
        _scale_kernel,
        out_shape=jax.ShapeDtypeStruct((r, LANE), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(x, jnp.asarray(denom, jnp.float32).reshape(1, 1))
