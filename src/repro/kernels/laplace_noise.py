"""Laplace noise from uniform bits — tiled Pallas kernel.

Transform: u = (bits >> 8) * 2^-24 in [0, 1); c = u - 0.5;
           n = -scale * sign(c) * log(1 - 2|c|).
The 24-bit integer goes to float through int32 (exact below 2^31): Mosaic
has no uint32 -> float32 conversion.

Tile shape (TILE_ROWS, 128): the last dim matches the TPU lane width and the
row count keeps the tile a multiple of the float32 (8, 128) packing. Scalars
ride in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
LANE_ROWS = 8
TILE_ROWS = 64  # (64, 128) f32 tile = 32 KiB VMEM per operand


def _laplace_transform(bits: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    top24 = jax.lax.bitcast_convert_type(bits >> 8, jnp.int32)
    u = top24.astype(jnp.float32) * (1.0 / (1 << 24))
    c = u - 0.5
    mag = jnp.maximum(1.0 - 2.0 * jnp.abs(c), 1e-30)
    return -scale * jnp.sign(c) * jnp.log(mag)


def fold_rows(x: jnp.ndarray) -> jnp.ndarray:
    """(TILE_ROWS, 128) -> (LANE_ROWS, 128) partial sums: one lane-aligned
    block per grid step, summed outside the kernel."""
    return jnp.sum(x.reshape(TILE_ROWS // LANE_ROWS, LANE_ROWS, LANE), axis=0)


# Whole-array SMEM operand for a kernel's scalars, kept 2-D so that a vmapped
# call's (batch, 1, k) block still ends in the array's own last two dims.
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(bits_ref, scale_ref, o_ref):
    o_ref[...] = _laplace_transform(bits_ref[...], scale_ref[0, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def laplace_from_bits(bits: jnp.ndarray, scale: jnp.ndarray, *,
                      interpret: bool) -> jnp.ndarray:
    """bits: (R, 128) uint32, R a multiple of TILE_ROWS; scale: scalar f32."""
    r, lane = bits.shape
    assert lane == LANE and r % TILE_ROWS == 0, (r, lane)
    grid = (r // TILE_ROWS,)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((r, LANE), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0)),
        interpret=interpret,
    )(bits, jnp.asarray(scale, jnp.float32).reshape(1, 1))
