"""Shared TPU tiling helpers for the one-hot-matmul kernels.

- Per-block statistics (a Min-Max range) are one ``(lo, hi)`` pair per grid
  step.  They are stored as an ``(n_blocks, 1, 2)`` array and fetched into
  scalar memory (SMEM) one ``(1, 1, 2)`` block per step: the TPU lowering
  refuses rank-1 ``(1,)`` blocks and 2-D ``(1, 1)`` blocks, and a whole
  statistics array (67M edges = 65,536 blocks) would crowd the small SMEM.
- Lane-major blocks (the last dimension) are multiples of 128; row blocks
  are multiples of 8.
- The one-hot matmuls are exact sums only when the MXU keeps f32 inputs at
  f32: ``mxu_precision`` asks for that explicitly, since the chip's default
  may round f32 operands to bf16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def lane_block(requested: int, extent: int) -> int:
    """Block along a lane-major axis: ``requested`` capped at the extent
    rounded up to a whole lane tile (small inputs get one padded block)."""
    return min(requested, round_up(max(extent, 1), LANE))


def row_block(requested: int, extent: int) -> int:
    """Block along a sublane-major axis, capped at the extent rounded up to
    a whole sublane tile."""
    return min(requested, round_up(max(extent, 1), SUBLANE))


def block_stats(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Pack per-block ranges into the ``(n_blocks, 1, 2)`` SMEM layout;
    the kernel reads ``ref[0, 0, 0]`` (lo) and ``ref[0, 0, 1]`` (hi)."""
    return jnp.stack([lo, hi], axis=-1).astype(jnp.int32)[:, None, :]


def stat_spec(block_of) -> pl.BlockSpec:
    """The grid step's ``(lo, hi)`` pair in SMEM; ``block_of(i, j)`` names
    the statistics row the step reads."""
    return pl.BlockSpec((1, 1, 2), lambda i, j: (block_of(i, j), 0, 0),
                        memory_space=pltpu.SMEM)


def mxu_precision(dtype) -> jax.lax.Precision:
    """Full f32 MXU passes for f32 operands; native passes otherwise."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT
