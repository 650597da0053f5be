"""Pallas TPU kernel for the EdgeScan aggregation hot path.

Computes ``out[n] = sum_{e: dst[e]==n} values[e]`` — the segment reduction at
the heart of GraphLake's edge-centric EdgeScan (paper §6.1), of GNN message
passing, and of the accumulator combine step.

TPU adaptation (DESIGN.md §2): the CPU engine's per-edge scatter becomes a
**block one-hot matmul** so the MXU does the scatter: for an edge block ``j``
and an output row block ``i``,

    out[i]  +=  onehot(i*BLOCK_N + rows == dst_j)  @  values_j      (MXU matmul)

The one-hot is built row-major, (BLOCK_N, BLOCK_E), from the edge block's
destination ids laid out as one lane-major row, so the matmul needs no
transposed operand.

The paper's Min-Max portion pruning (§5.3) maps to a per-edge-block skip:
each edge block carries min/max(dst) (one SMEM pair per grid step);
blocks whose range misses the output block are skipped with ``@pl.when`` —
the same "most effective when the edge table is sorted by the FK" property
the paper notes, because sorted edges make block ranges narrow.

Grid: (n_out_blocks, n_edge_blocks), edge blocks innermost so each output
block stays resident in VMEM while every edge block streams past it once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import (block_stats, lane_block, mxu_precision, round_up,
                                  row_block, stat_spec)

DEFAULT_BLOCK_E = 1024   # edges per block  (8*128-aligned)
DEFAULT_BLOCK_N = 512    # output rows per block
_NEG = -1                # padding dst id: matches no output row


def _kernel(stats_ref, dst_ref, val_ref, out_ref, *, block_n: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    row_lo = i * block_n
    overlaps = (stats_ref[0, 0, 1] >= row_lo) & (stats_ref[0, 0, 0] < row_lo + block_n)

    @pl.when(overlaps)
    def _accumulate():
        dst = dst_ref[...]                                   # (1, block_e)
        block_e = dst.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_e), 0) + row_lo
        onehot = (dst == rows).astype(val_ref.dtype)         # (block_n, block_e)
        out_ref[...] += jnp.dot(
            onehot, val_ref[...], preferred_element_type=jnp.float32,
            precision=mxu_precision(val_ref.dtype),
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "block_e", "block_n", "interpret"),
)
def edge_segment_sum_pallas(
    values: jax.Array,
    dst: jax.Array,
    num_segments: int,
    block_e: int = DEFAULT_BLOCK_E,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Pallas segment-sum. values: (E, D) float; dst: (E,) int32 in [0, N)."""
    e, d = values.shape
    n = num_segments
    block_e = lane_block(block_e, e)
    block_n = row_block(block_n, n)
    e_pad = round_up(max(e, 1), block_e)
    n_pad = round_up(max(n, 1), block_n)
    if e_pad != e:
        values = jnp.pad(values, ((0, e_pad - e), (0, 0)))
        dst = jnp.pad(dst, (0, e_pad - e), constant_values=_NEG)
    dst = dst.astype(jnp.int32)

    n_eblk = e_pad // block_e
    n_nblk = n_pad // block_n
    dst_blocks = dst.reshape(n_eblk, block_e)
    # per-edge-block Min-Max statistics (paper §5.3); padding (_NEG) is
    # excluded from min so sorted inputs keep tight ranges.
    stats = block_stats(jnp.where(dst_blocks >= 0, dst_blocks, n_pad).min(axis=1),
                        dst_blocks.max(axis=1))

    out = pl.pallas_call(
        functools.partial(_kernel, block_n=block_n),
        grid=(n_nblk, n_eblk),
        in_specs=[
            stat_spec(lambda i, j: j),                          # dst min/max
            pl.BlockSpec((1, block_e), lambda i, j: (0, j)),    # dst ids (lane row)
            pl.BlockSpec((block_e, d), lambda i, j: (j, 0)),    # edge values
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret,
    )(stats, dst.reshape(1, e_pad), values)
    return out[:n].astype(values.dtype)
