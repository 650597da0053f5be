"""Public kernel API with backend dispatch.

Every op has a pure-jnp reference path (``ref.py``) — used on CPU/GPU and for
the 512-device SPMD dry-run — and a Pallas TPU kernel selected when running
on TPU.  The dispatch contract:

    backend == tpu                        -> Pallas kernel (REPRO_PALLAS must
                                            be unset: nothing moves a chip
                                            run off its kernels)
    REPRO_PALLAS=interpret (non-TPU only) -> Pallas kernel in interpret mode
                                            (CPU execution of the kernel body;
                                            how kernels are validated here)
    otherwise                             -> jnp reference

``csr_segment_sum`` off the kernel is the one exception: it derives its
segment ids in one linear pass, where its reference binary-searches them.

Every kernel-backed op carries a ``custom_vjp`` whose backward pass is
written in jnp, so trainers differentiate through the kernels on the chip
exactly as they do through the references on the CPU.

All ops are shape-polymorphic jit-stable functions safe to call inside
pjit/shard_map-traced code.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.csr_expand import csr_segment_sum_pallas
from repro.kernels.edge_scan import edge_segment_sum_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def _mode() -> str:
    forced = os.environ.get("REPRO_PALLAS", "").lower()
    on_tpu = jax.default_backend() == "tpu"
    if forced not in ("", "interpret", "off"):
        raise ValueError(f"REPRO_PALLAS={forced!r}: expected 'interpret' or 'off'")
    if forced and on_tpu:
        raise RuntimeError(
            f"REPRO_PALLAS={forced} on a TPU backend would move the kernels off "
            f"the chip; unset it (it selects interpret/reference mode for CPU "
            f"validation only)")
    if forced:
        return forced
    return "tpu" if on_tpu else "off"


def use_pallas() -> bool:
    return _mode() in ("tpu", "interpret")


def _interpret() -> bool:
    return _mode() == "interpret"


# When True, the jnp attention path unrolls its kv-block scan so that
# compiled-cost analysis counts every block (cost_analysis counts loop bodies
# once).  Set by the dry-run's cost-variant compiles only.
_ATTN_UNROLL = False


class attention_unroll:
    """Context manager: unroll attention kv scans for exact cost analysis."""

    def __enter__(self):
        global _ATTN_UNROLL
        self._prev = _ATTN_UNROLL
        _ATTN_UNROLL = True

    def __exit__(self, *exc):
        global _ATTN_UNROLL
        _ATTN_UNROLL = self._prev


# ---------------------------------------------------------------------------
# segment reductions
# ---------------------------------------------------------------------------

def segment_sum(values: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    """1-D or 2-D segment sum. Dispatches the 2-D case to the Pallas kernel."""
    if values.ndim == 2 and use_pallas():
        return edge_segment_sum(values, segment_ids, num_segments)
    return jax.ops.segment_sum(values, segment_ids, num_segments=num_segments)


def segment_min(values: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    return jax.ops.segment_min(values, segment_ids, num_segments=num_segments)


def segment_max(values: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    return jax.ops.segment_max(values, segment_ids, num_segments=num_segments)


def segment_mean(values: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    ones = jnp.ones(values.shape[:1], dtype=values.dtype)
    counts = jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)
    total = segment_sum(values, segment_ids, num_segments)
    denom = jnp.maximum(counts, 1)
    return total / (denom[:, None] if values.ndim == 2 else denom)


def _rows_at(g: jax.Array, seg: jax.Array, num_segments: int) -> jax.Array:
    """``g[seg]`` with rows outside ``[0, num_segments)`` zeroed: the
    cotangent of a segment sum, whose padding/out-of-range ids were dropped
    in the forward pass."""
    valid = (seg >= 0) & (seg < num_segments)
    rows = g[jnp.clip(seg, 0, num_segments - 1)]
    return jnp.where(valid[:, None], rows, jnp.zeros_like(rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _edge_segment_sum_kernel(values, dst, num_segments):
    return edge_segment_sum_pallas(values, dst, num_segments, interpret=_interpret())


def _edge_segment_sum_fwd(values, dst, num_segments):
    return _edge_segment_sum_kernel(values, dst, num_segments), dst


def _edge_segment_sum_bwd(num_segments, dst, g):
    return _rows_at(g, dst, num_segments), None


_edge_segment_sum_kernel.defvjp(_edge_segment_sum_fwd, _edge_segment_sum_bwd)


def edge_segment_sum(values: jax.Array, dst: jax.Array, num_segments: int) -> jax.Array:
    """(E, D) edge values scattered-added to (N, D). The EdgeScan hot path."""
    if use_pallas():
        return _edge_segment_sum_kernel(values, dst, num_segments)
    return _ref.edge_segment_sum(values, dst, num_segments)


def masked_edge_segment_sum(values, src, dst, frontier, num_segments: int) -> jax.Array:
    mask = frontier[src].astype(values.dtype)
    return edge_segment_sum(values * mask[:, None], dst, num_segments)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _csr_segment_sum_kernel(values, indptr, num_segments):
    return csr_segment_sum_pallas(values, indptr, num_segments, interpret=_interpret())


# arcs per row of the blocked running sum in ``_csr_segment_ids``: the TPU
# compiler takes time that grows with the length of a one-axis cumsum (about
# 10 s at 63.5M arcs), and about a second for rows this wide
_ID_BLOCK = 1024


def _csr_segment_ids(indptr: jax.Array, e: int) -> jax.Array:
    """Each of ``e`` arcs' owning range in a sorted ``indptr`` (N+1,):
    ``searchsorted(indptr, arange(e), side="right") - 1``, in one linear pass.

    Every range start marks its position, and an arc's running count of
    marks is one more than its range.  An empty range marks the position of
    the next one again; starts at or past ``e`` are dropped.  So arcs before
    ``indptr[0]`` get -1 and arcs at or past ``indptr[N]`` get N, both
    outside ``[0, N)``.  The ids are non-decreasing.

    The running count is blocked: a cumsum within rows of ``_ID_BLOCK``
    marks, plus the exclusive cumsum of the row totals (exact in int32)."""
    rows = -(-e // _ID_BLOCK)
    # a start at or past e moves past the padding too, and stays sorted; the
    # sorted hint spares the TPU compiler a sort of the starts (about 20 s of
    # compilation at 1.2M offsets)
    starts = jnp.where(indptr < e, indptr, rows * _ID_BLOCK)
    marks = jnp.zeros(rows * _ID_BLOCK, jnp.int32).at[starts].add(
        1, mode="drop", indices_are_sorted=True)
    inner = jnp.cumsum(marks.reshape(rows, _ID_BLOCK), axis=1)
    before = jnp.cumsum(inner[:, -1]) - inner[:, -1]
    return (inner + before[:, None]).reshape(-1)[:e] - 1


def _csr_segment_sum_fwd(values, indptr, num_segments):
    # arcs outside every range (ids -1 and N) are masked in the backward pass
    seg = _csr_segment_ids(indptr, values.shape[0])
    return _csr_segment_sum_kernel(values, indptr, num_segments), seg


def _csr_segment_sum_bwd(num_segments, seg, g):
    return _rows_at(g, seg, num_segments), None


_csr_segment_sum_kernel.defvjp(_csr_segment_sum_fwd, _csr_segment_sum_bwd)


def csr_segment_sum(values: jax.Array, indptr: jax.Array, num_segments: int) -> jax.Array:
    """Segment sum over CSR offset ranges: values pre-sorted by owning
    segment, indptr (N+1,).  The topology plane's vertex-centric hot path —
    accepts (E,) or (E, D) values; 1-D input returns a 1-D result.

    Like ``segment_sum``, only the 2-D case dispatches to the Pallas
    one-hot-matmul kernel — a single value column would waste the MXU.
    Every other case derives each arc's segment id from the offsets in one
    linear pass (``_csr_segment_ids``) and scatters with sorted ids; the
    reference's binary search would gather from ``indptr`` at every arc
    once per halving.
    """
    if values.ndim == 2 and use_pallas():
        return _csr_segment_sum_kernel(values, indptr, num_segments)
    seg = _csr_segment_ids(indptr, values.shape[0])
    return jax.ops.segment_sum(values, seg, num_segments=num_segments,
                               indices_are_sorted=True)


def stacked_segment_sum(values: jax.Array, segment_ids: jax.Array,
                        num_segments: int) -> jax.Array:
    """Segment sum for a *stack* of riders sharing one edge stream.

    ``values`` is (R, E) — R riders' per-edge contributions over the same
    (E,) ``segment_ids`` (the shared-scan batch layout: dead rider/edge
    pairs pre-zeroed by the caller's ``alive`` mask).  Returns (R, N).

    One transpose turns this into the (E, D) layout ``segment_sum`` already
    dispatches to the Pallas edge kernel, with riders riding the feature
    axis — the batch reuses the solo kernel instead of growing a new one.
    """
    return segment_sum(values.T, segment_ids, num_segments).T


# ---------------------------------------------------------------------------
# pytree stacking (batched rider state)
# ---------------------------------------------------------------------------

def tree_stack(trees: list):
    """Stack a list of identically-structured pytrees leaf-wise: R trees of
    (leaf_shape) -> one tree of (R, *leaf_shape).  The shared-scan batch
    path uses this to run R riders' frontier/accumulator state through one
    traced program."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def tree_unstack(tree) -> list:
    """Inverse of :func:`tree_stack`: one tree of (R, *leaf_shape) back to
    a list of R per-rider trees."""
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[0]
    return [treedef.unflatten([leaf[i] for leaf in leaves]) for i in range(n)]


# ---------------------------------------------------------------------------
# embedding bag
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _embedding_bag_kernel(table, indices, weights):
    return embedding_bag_pallas(table, indices, weights, interpret=_interpret())


def _embedding_bag_fwd(table, indices, weights):
    return _embedding_bag_kernel(table, indices, weights), (table, indices, weights)


def _embedding_bag_bwd(res, g):
    """out[b] = sum_l w[b,l] table[idx[b,l]]; indices outside [0, V) match
    no row in the kernel, so they get no table or weight gradient."""
    table, indices, weights = res
    v, d = table.shape
    valid = (indices >= 0) & (indices < v)                       # (B, L)
    rows = jnp.where(valid, indices, v).reshape(-1)              # v = dropped
    per_slot = weights.astype(g.dtype)[..., None] * g[:, None, :]  # (B, L, D)
    d_table = jax.ops.segment_sum(per_slot.reshape(-1, d), rows, num_segments=v)
    gathered = table[jnp.clip(indices, 0, v - 1)]                # (B, L, D)
    d_weights = jnp.where(valid, (gathered * g[:, None, :]).sum(-1), 0.0)
    return d_table.astype(table.dtype), None, d_weights.astype(weights.dtype)


_embedding_bag_kernel.defvjp(_embedding_bag_fwd, _embedding_bag_bwd)


def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    weights: jax.Array | None = None,
    mode: str = "sum",
) -> jax.Array:
    """EmbeddingBag: (V, D) table, (B, L) indices -> (B, D)."""
    if weights is None:
        weights = jnp.ones(indices.shape, dtype=table.dtype)
    if use_pallas():
        out = _embedding_bag_kernel(table, indices, weights)
        if mode == "mean":
            denom = jnp.maximum(weights.sum(axis=1, keepdims=True), 1e-9)
            out = out / denom.astype(out.dtype)
        return out
    return _ref.embedding_bag(table, indices, weights, mode=mode)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_kernel(q, k, v, kv_len_mask, causal, block_q, block_kv):
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_kv=block_kv, interpret=_interpret(),
                                  kv_len_mask=kv_len_mask)


def _flash_fwd(q, k, v, kv_len_mask, causal, block_q, block_kv):
    out = _flash_kernel(q, k, v, kv_len_mask, causal, block_q, block_kv)
    return out, (q, k, v, kv_len_mask)


def _flash_bwd(causal, block_q, block_kv, res, g):
    """Backward through the streaming-softmax jnp reference (same masking
    and decode alignment as the kernel), recomputed from q, k, v."""
    q, k, v, kv_len_mask = res
    _, vjp = jax.vjp(
        lambda q, k, v: _ref.attention_blockwise(
            q, k, v, causal=causal, block_kv=block_kv, kv_len_mask=kv_len_mask),
        q, k, v)
    return (*vjp(g), None)


_flash_kernel.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    block_q: int = 512, block_kv: int = 512, kv_len_mask=None,
) -> jax.Array:
    """Memory-safe attention. q,k,v: (B, H, S, Dh), H pre-expanded for GQA.
    ``kv_len_mask``: optional traced scalar masking keys >= it.

    With Pallas on, a sequence length that does not divide its block is an
    error: the kernel never hands such a shape to the reference quietly."""
    q_len, kv_len = q.shape[2], k.shape[2]
    block_q, block_kv = min(block_q, q_len), min(block_kv, kv_len)
    if use_pallas():
        if q_len % block_q or kv_len % block_kv:
            raise ValueError(
                f"flash_attention: q_len={q_len} / kv_len={kv_len} do not divide "
                f"blocks ({block_q}, {block_kv}); pad the sequence to a block "
                f"multiple")
        return _flash_kernel(q, k, v, kv_len_mask, causal, block_q, block_kv)
    from repro.perf_flags import enabled
    if (enabled("tri") and causal and kv_len_mask is None
            and q_len == kv_len and q_len % block_kv == 0
            and q_len // block_kv >= 2):
        return _ref.attention_triangular(q, k, v, causal=True, block=block_kv,
                                         unroll=_ATTN_UNROLL)
    return _ref.attention_blockwise(q, k, v, causal=causal, block_kv=block_kv,
                                    kv_len_mask=kv_len_mask,
                                    unroll=_ATTN_UNROLL)
