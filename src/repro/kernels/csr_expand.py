"""Pallas TPU kernel for the CSR frontier-expand aggregation path.

Computes ``out[v] = sum_{e in [indptr[v], indptr[v+1])} values[e]`` — the
vertex-centric counterpart of the edge-centric ``edge_scan`` kernel: edge
values arrive **pre-sorted by destination** (the topology plane's reverse-CSR
order, DESIGN.md §3), so segment membership is an *offset range* instead of a
scattered id array.

TPU adaptation (DESIGN.md §2): like the edge-scan kernel, the per-edge
scatter becomes a block one-hot matmul so the MXU does the segment gather.
For edge block ``j`` and output row block ``i``,

    onehot[v, e] = (start[v] <= e_global < end[v])          (VPU compare)
    out[i]      += onehot @ values_j                        (MXU matmul)

where ``start``/``end`` are the vertex block's indptr slices.  Because
offsets are sorted, the block-skip test is **exact** rather than a Min-Max
heuristic: edge block ``j`` intersects output block ``i`` iff the half-open
ranges ``[j*BLOCK_E, (j+1)*BLOCK_E)`` and ``[start[first], end[last])``
overlap — every skipped (i, j) pair provably contributes nothing.  This is
the tight-range property that dst-sorted edge order buys (the same property
that narrows the edge-scan kernel's Min-Max ranges on FK-sorted tables).

Grid: (n_out_blocks, n_edge_blocks), edge blocks innermost so each output
block stays resident in VMEM while its edge range streams past.
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


def _kernel(stats_ref, starts_ref, ends_ref, val_ref, out_ref,
            *, block_e: int, block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    e_lo = j * block_e
    # exact range-overlap skip: sorted offsets make this provably lossless
    overlaps = (stats_ref[0, 0, 1] > e_lo) & (stats_ref[0, 0, 0] < e_lo + block_e)

    @pl.when(overlaps)
    def _accumulate():
        starts = starts_ref[...]                              # (block_n, 1)
        ends = ends_ref[...]                                  # (block_n, 1)
        eidx = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_e), 1) + e_lo
        onehot = ((eidx >= starts) & (eidx < ends)).astype(
            val_ref.dtype
        )                                                     # (block_n, block_e)
        out_ref[...] += jnp.dot(
            onehot, val_ref[...], preferred_element_type=jnp.float32,
            precision=mxu_precision(val_ref.dtype),
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "block_e", "block_n", "interpret"),
)
def csr_segment_sum_pallas(
    values: jax.Array,
    indptr: jax.Array,
    num_segments: int,
    block_e: int = DEFAULT_BLOCK_E,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Pallas CSR segment-sum.

    values: (E, D) float, sorted by owning segment; indptr: (N+1,) int with
    ``indptr[0] == 0`` and ``indptr[N] == E``; returns (N, D) float.
    """
    e, d = values.shape
    n = num_segments
    block_e = lane_block(block_e, e)
    block_n = row_block(block_n, n)
    e_pad = round_up(max(e, 1), block_e)
    n_pad = round_up(max(n, 1), block_n)
    if e_pad != e:
        values = jnp.pad(values, ((0, e_pad - e), (0, 0)))

    indptr = indptr.astype(jnp.int32)
    starts = indptr[:-1]
    ends = indptr[1:]
    if n_pad != n:
        # padded output rows own the empty range [E, E)
        starts = jnp.pad(starts, (0, n_pad - n), constant_values=e)
        ends = jnp.pad(ends, (0, n_pad - n), constant_values=e)

    n_eblk = e_pad // block_e
    n_nblk = n_pad // block_n
    # per-output-block edge range: offsets are sorted, so it is exactly
    # [starts[first], ends[last]) — block min/max without a reduction scan
    starts_blocks = starts.reshape(n_nblk, block_n)
    ends_blocks = ends.reshape(n_nblk, block_n)
    stats = block_stats(starts_blocks[:, 0], ends_blocks[:, -1])

    out = pl.pallas_call(
        functools.partial(_kernel, block_e=block_e, block_n=block_n),
        grid=(n_nblk, n_eblk),
        in_specs=[
            stat_spec(lambda i, j: i),                          # edge range
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),    # range starts
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),    # range ends
            pl.BlockSpec((block_e, d), lambda i, j: (j, 0)),    # edge values
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret,
    )(stats, starts.reshape(n_pad, 1), ends.reshape(n_pad, 1), values)
    return out[:n].astype(values.dtype)
