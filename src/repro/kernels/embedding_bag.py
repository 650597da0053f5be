"""Pallas TPU kernel for EmbeddingBag (gather + weighted segment-sum).

RecSys lookup = GraphLake vertex-property fetch: ``out[b] = sum_l w[b,l] *
table[idx[b,l]]``.  JAX has no native EmbeddingBag (kernel taxonomy §B.6);
this is the TPU-native one.

TPU adaptation: like ``edge_scan``, the gather becomes an MXU matmul — for a
batch block ``i`` and a vocab block ``j``:

    M[b, v]  = sum_l w[b,l] * (idx[b,l] == j*BLOCK_V + v)     (VPU compares)
    out[i]  +=  M @ table_j                                    (MXU matmul)

with per-batch-block min/max(idx) pruning so only vocab blocks actually
referenced are visited (row-sharded tables keep index ranges narrow — the
same locality GraphLake's transformed IDs create for vertex files).

Grid: (n_batch_blocks, n_vocab_blocks), vocab innermost (out block resident).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import block_stats, mxu_precision, round_up, row_block, stat_spec

DEFAULT_BLOCK_B = 256
DEFAULT_BLOCK_V = 512


def _kernel(stats_ref, idx_ref, w_ref, table_ref, out_ref, *, block_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v_lo = j * block_v
    overlaps = (stats_ref[0, 0, 1] >= v_lo) & (stats_ref[0, 0, 0] < v_lo + block_v)

    @pl.when(overlaps)
    def _accumulate():
        block_b, bag = idx_ref.shape
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_b, block_v), 1) + v_lo
        m = jnp.zeros((block_b, block_v), dtype=jnp.float32)
        # the bag length is static and small: one static (block_b, 1) column
        # per slot, compared against the vocab block's lane iota
        for l in range(bag):
            hit = (idx_ref[:, l:l + 1] == cols).astype(jnp.float32)
            m = m + hit * w_ref[:, l:l + 1]
        out_ref[...] += jnp.dot(
            m, table_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=mxu_precision(jnp.float32),
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_v", "interpret")
)
def embedding_bag_pallas(
    table: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    block_b: int = DEFAULT_BLOCK_B,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool = False,
) -> jax.Array:
    """table: (V, D); indices: (B, L) int32; weights: (B, L). Returns (B, D).

    Padding entries must carry weight 0 (their index value is then irrelevant
    but should stay in range or -1).
    """
    v, d = table.shape
    b, bag = indices.shape
    block_b = row_block(block_b, b)
    block_v = row_block(block_v, v)
    b_pad = round_up(b, block_b)
    v_pad = round_up(v, block_v)
    if b_pad != b:
        indices = jnp.pad(indices, ((0, b_pad - b), (0, 0)), constant_values=-1)
        weights = jnp.pad(weights, ((0, b_pad - b), (0, 0)))
    if v_pad != v:
        table = jnp.pad(table, ((0, v_pad - v), (0, 0)))
    indices = indices.astype(jnp.int32)

    n_bblk = b_pad // block_b
    n_vblk = v_pad // block_v
    idx_blocks = indices.reshape(n_bblk, block_b * bag)
    live = (weights.reshape(n_bblk, block_b * bag) != 0) & (idx_blocks >= 0)
    stats = block_stats(jnp.where(live, idx_blocks, v_pad).min(axis=1),
                        jnp.where(live, idx_blocks, -1).max(axis=1))

    out = pl.pallas_call(
        functools.partial(_kernel, block_v=block_v),
        grid=(n_bblk, n_vblk),
        in_specs=[
            stat_spec(lambda i, j: i),                            # index min/max
            pl.BlockSpec((block_b, bag), lambda i, j: (i, 0)),    # indices
            pl.BlockSpec((block_b, bag), lambda i, j: (i, 0)),    # weights
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),      # table tile
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, d), jnp.float32),
        interpret=interpret,
    )(stats, indices, weights.astype(jnp.float32), table)
    return out[:b].astype(table.dtype)
