"""Fused DPPS round point-op (Alg. 1 lines 3+5 and the Eq. 22 norms).

Per tile, in one VMEM pass:
    noise      = Laplace(bits; scale)           (inverse CDF)
    s_noise    = s + eps + gamma_n * noise
    eps_l1[i]  = sum |eps_tile|                 (per-grid-step (8, 128)
    noise_l1[i]= sum |noise_tile|                partials, summed outside)

Unfused this is 4 reads + 1 write + 2 full reduction passes over d_s; fused
it is 3 reads + 1 write with on-chip accumulators. At DPPS's once-per-round
cadence over the full shared tree, the memory term of the protocol overhead
drops ~2.3x (see EXPERIMENTS.md SPerf).
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
    _laplace_transform,
    fold_rows,
)


def _kernel(s_ref, eps_ref, bits_ref, scalars_ref, o_ref, eps_l1_ref, noise_l1_ref):
    scale = scalars_ref[0, 0]
    gamma_n = scalars_ref[0, 1]
    noise = _laplace_transform(bits_ref[...], scale)
    eps = eps_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    o_ref[...] = (s + eps + gamma_n * noise).astype(o_ref.dtype)
    eps_l1_ref[...] = fold_rows(jnp.abs(eps))
    noise_l1_ref[...] = fold_rows(jnp.abs(noise))


@functools.partial(jax.jit, static_argnames=("interpret",))
def dpps_perturb(s: jnp.ndarray, eps: jnp.ndarray, bits: jnp.ndarray,
                 scale: jnp.ndarray, gamma_n: jnp.ndarray, *,
                 interpret: bool):
    """All tensor args (R, 128), R multiple of TILE_ROWS.

    Returns (s_noise (R,128), eps_l1 scalar, noise_l1 scalar).
    """
    r, lane = s.shape
    assert lane == LANE and r % TILE_ROWS == 0, (r, lane)
    grid = (r // TILE_ROWS,)
    scalars = jnp.stack([jnp.asarray(scale, jnp.float32),
                         jnp.asarray(gamma_n, jnp.float32)]).reshape(1, 2)
    s_noise, eps_l1, noise_l1 = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((r, LANE), s.dtype),
            jax.ShapeDtypeStruct((grid[0] * LANE_ROWS, LANE), jnp.float32),
            jax.ShapeDtypeStruct((grid[0] * LANE_ROWS, LANE), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
            SMEM_SPEC,
        ],
        out_specs=(
            pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((LANE_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((LANE_ROWS, LANE), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(s, eps, bits, scalars)
    return s_noise, jnp.sum(eps_l1), jnp.sum(noise_l1)
