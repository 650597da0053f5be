"""Graph algorithms over the engine (paper Table 2: PR, WCC, CDLP, LCC, BFS).

All five run on the *topology only* (no property access), consuming the
**topology plane** (DESIGN.md §3) directly:

- whole-graph scans (PR, WCC, CDLP, LCC) take the plane's **dst-sorted CSR
  edge order** — segment ids arrive non-decreasing, so the Pallas segment
  kernels see tight per-block ranges and skip every non-overlapping
  (edge-block, output-block) pair;
- PageRank's inner reduction is the CSR offset-range segment sum
  (``kops.csr_segment_sum``), fed by the reverse-CSR index — no per-edge
  destination ids are stored or uploaded.  For its 1-D rank column the op
  derives each arc's segment id from the offsets in one linear pass on the
  device; the Pallas offset-range kernel serves the 2-D (multi-channel)
  form of the same op;
- BFS dispatches adaptively per level, exactly like EdgeScan: small
  frontiers expand through CSR adjacency ranges, large frontiers fall back
  to the edge-centric masked scan.

The numeric inner loops are jitted JAX (dispatching to the Pallas kernels on
TPU via ``repro.kernels.ops``); convergence control stays in Python exactly
like GSQL's WHILE drives supersteps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.perf_flags import enabled as perf_enabled
from repro.tracing import scope, span


def _csr_for(engine, edge_type: str, n: int):
    """The edge type's CSR when the ``csr`` perf flag is on (the baseline
    ``REPRO_OPTS=""`` run must not build or consume CSR at all) and its
    vertex spaces match ``n`` (callers may override ``n`` for truncated
    runs — then fall back to edge arrays)."""
    if not perf_enabled("csr"):
        return None
    et = engine.schema.edge_types[edge_type]
    topo = engine.topology
    # dimension check BEFORE building: a truncated run must not pay the
    # grouping cost of an index it cannot use
    if topo.n_vertices(et.src_type) != n or topo.n_vertices(et.dst_type) != n:
        return None
    return engine.plane.csr(edge_type)


def _edges_dst_sorted(engine, edge_type: str, n: int):
    """(src, dst) in dst-sorted order when CSR dims match, else raw concat."""
    csr = _csr_for(engine, edge_type, n)
    if csr is not None:
        return engine.plane.edges_by_dst(edge_type)
    return engine.concat_edges(edge_type)


# ---------------------------------------------------------------------------
# PageRank
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n",))
def _pagerank_step_csr(rank, rev_src, rev_indptr, out_deg, n: int, damping: float):
    with scope("pagerank.gather"):
        contrib = rank[rev_src] / jnp.maximum(out_deg[rev_src], 1.0)
    with scope("pagerank.segment_sum"):
        agg = kops.csr_segment_sum(contrib, rev_indptr, n)
    with scope("pagerank.dangling"):
        # dangling mass (vertices with no out-edges) redistributes uniformly
        dangling = jnp.where(out_deg > 0, 0.0, rank).sum()
    return (1.0 - damping) / n + damping * (agg + dangling / n)


@functools.partial(jax.jit, static_argnames=("n",))
def _pagerank_step(rank, src, dst, out_deg, n: int, damping: float):
    with scope("pagerank.gather"):
        contrib = rank[src] / jnp.maximum(out_deg[src], 1.0)
    with scope("pagerank.segment_sum"):
        agg = kops.segment_sum(contrib, dst, n)
    with scope("pagerank.dangling"):
        dangling = jnp.where(out_deg > 0, 0.0, rank).sum()
    return (1.0 - damping) / n + damping * (agg + dangling / n)


def _upload(*arrays) -> tuple:
    """Host arrays onto the device, waited for, in a ``pagerank.upload``
    span that carries their bytes."""
    with span("pagerank.upload") as s:
        out = jax.block_until_ready(tuple(jnp.asarray(a, dtype=d) for a, d in arrays))
        s.set_metadata(bytes=sum(int(x.nbytes) for x in out))
    return out


def pagerank(engine, edge_type: str, n: int | None = None, damping: float = 0.85,
             max_iters: int = 20, tol: float = 1e-7) -> np.ndarray:
    et = engine.schema.edge_types[edge_type]
    n = n or engine.topology.n_vertices(et.src_type)
    csr = _csr_for(engine, edge_type, n)
    if csr is not None:
        rev_src, rev_indptr, out_deg = _upload(
            (csr.rev_src, jnp.int32), (csr.rev_indptr, jnp.int32),
            (csr.degrees("out"), jnp.float32))
        step = lambda r: _pagerank_step_csr(r, rev_src, rev_indptr, out_deg, n, damping)
    else:
        src, dst = engine.concat_edges(edge_type)
        src_j, dst_j = _upload((src, jnp.int32), (dst, jnp.int32))
        out_deg = kops.segment_sum(jnp.ones_like(src_j, dtype=jnp.float32), src_j, n)
        step = lambda r: _pagerank_step(r, src_j, dst_j, out_deg, n, damping)
    rank = jnp.full(n, 1.0 / n, dtype=jnp.float32)
    for i in range(max_iters):
        with span("pagerank.superstep", step=i):
            new = step(rank)
            with span("pagerank.sync"):
                done = float(jnp.abs(new - rank).sum()) < tol
        rank = new
        if done:
            break
    with span("pagerank.readback"):
        return np.asarray(rank)


# ---------------------------------------------------------------------------
# Weakly Connected Components (label propagation to minimum)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n",))
def _wcc_step(labels, src, dst, n: int):
    fwd = kops.segment_min(labels[src], dst, n)
    bwd = kops.segment_min(labels[dst], src, n)
    return jnp.minimum(labels, jnp.minimum(fwd, bwd))


def wcc(engine, edge_type: str, n: int | None = None, max_iters: int = 200) -> np.ndarray:
    et = engine.schema.edge_types[edge_type]
    n = n or engine.topology.n_vertices(et.src_type)
    src, dst = _edges_dst_sorted(engine, edge_type, n)
    src_j = jnp.asarray(src, dtype=jnp.int32)
    dst_j = jnp.asarray(dst, dtype=jnp.int32)
    labels = jnp.arange(n, dtype=jnp.int32)
    for _ in range(max_iters):
        new = _wcc_step(labels, src_j, dst_j, n)
        if bool(jnp.array_equal(new, labels)):
            break
        labels = new
    return np.asarray(labels)


# ---------------------------------------------------------------------------
# Community Detection via Label Propagation (CDLP)
# ---------------------------------------------------------------------------

def cdlp(engine, edge_type: str, n: int | None = None, iterations: int = 10) -> np.ndarray:
    """Synchronous LPA, Graphalytics semantics: each vertex adopts the most
    frequent neighbor label; ties break to the smallest label.

    Mode-per-vertex is a sort-and-count host-side pass (argmax over ragged
    groups).  The neighbor pairs come from the plane's dst-sorted CSR order,
    so each half of the undirected concatenation arrives pre-grouped by
    vertex and the per-iteration lexsort runs on nearly-sorted keys.
    """
    et = engine.schema.edge_types[edge_type]
    n = n or engine.topology.n_vertices(et.src_type)
    src, dst = _edges_dst_sorted(engine, edge_type, n)
    # undirected neighborhood: both edge directions contribute
    nbr_dst = np.concatenate([dst, src])
    nbr_src = np.concatenate([src, dst])
    labels = np.arange(n, dtype=np.int64)
    for _ in range(iterations):
        lab = labels[nbr_src]
        order = np.lexsort((lab, nbr_dst))
        v_sorted = nbr_dst[order]
        l_sorted = lab[order]
        # run-length encode (vertex, label) pairs
        boundary = np.empty(len(v_sorted), dtype=bool)
        if len(v_sorted):
            boundary[0] = True
            boundary[1:] = (v_sorted[1:] != v_sorted[:-1]) | (l_sorted[1:] != l_sorted[:-1])
        starts = np.flatnonzero(boundary)
        counts = np.diff(np.append(starts, len(v_sorted)))
        grp_v = v_sorted[starts]
        grp_l = l_sorted[starts]
        # per-vertex argmax count, ties -> smallest label: sort by
        # (vertex, -count, label) and take the first entry per vertex
        sel = np.lexsort((grp_l, -counts, grp_v))
        first = np.flatnonzero(
            np.concatenate(([True], grp_v[sel][1:] != grp_v[sel][:-1]))
        )
        winners_v = grp_v[sel][first]
        winners_l = grp_l[sel][first]
        new = labels.copy()
        new[winners_v] = winners_l
        if np.array_equal(new, labels):
            break
        labels = new
    return labels


# ---------------------------------------------------------------------------
# Local Clustering Coefficient
# ---------------------------------------------------------------------------

def lcc(engine, edge_type: str, n: int | None = None, block: int = 1024) -> np.ndarray:
    """LCC via blocked dense adjacency products (wedge-closure counting).

    Fine for benchmark-scale graphs (n <= ~32k); the Graphalytics semantics
    treat the graph as directed-ignored (undirected), no self-loops.
    """
    et = engine.schema.edge_types[edge_type]
    n = n or engine.topology.n_vertices(et.src_type)
    src, dst = _edges_dst_sorted(engine, edge_type, n)
    u = np.concatenate([src, dst])
    v = np.concatenate([dst, src])
    keep = u != v
    u, v = u[keep], v[keep]
    adj = np.zeros((n, n), dtype=np.float32)
    adj[u, v] = 1.0
    adj_j = jnp.asarray(adj)
    tri = np.zeros(n, dtype=np.float64)
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        # triangles through i = sum_j sum_k A[i,j] A[j,k] A[k,i] / 2
        paths2 = adj_j[lo:hi] @ adj_j                      # (b, n) 2-paths
        tri[lo:hi] = np.asarray((paths2 * adj_j[lo:hi]).sum(axis=1), dtype=np.float64) / 2.0
    deg = np.asarray(adj.sum(axis=1), dtype=np.float64)
    wedges = deg * (deg - 1) / 2.0
    with np.errstate(divide="ignore", invalid="ignore"):
        out = np.where(wedges > 0, tri / wedges, 0.0)
    return out


# ---------------------------------------------------------------------------
# BFS
# ---------------------------------------------------------------------------

def bfs(engine, edge_type: str, source_dense: int, n: int | None = None,
        directed: bool = True, max_depth: int = 10_000) -> np.ndarray:
    """Frontier BFS with per-level adaptive dispatch (DESIGN.md §3): small
    frontiers expand through CSR adjacency ranges (touch only incident
    edges), large frontiers use the edge-centric masked scan (sequential
    locality).  Returns int64 depths (-1 = unreached)."""
    et = engine.schema.edge_types[edge_type]
    n = n or engine.topology.n_vertices(et.src_type)
    csr = _csr_for(engine, edge_type, n)
    src, dst = engine.concat_edges(edge_type)
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    threshold = engine.plane.threshold()
    depth = np.full(n, -1, dtype=np.int64)
    depth[source_dense] = 0
    frontier_ids = np.array([source_dense], dtype=np.int64)
    for level in range(1, max_depth):
        if csr is not None and len(frontier_ids) <= threshold * n:
            _, cand, _ = csr.expand(frontier_ids, direction="out")
            if not directed:
                _, cand_in, _ = csr.expand(frontier_ids, direction="in")
                cand = np.concatenate([cand, cand_in])
        else:
            mask = np.zeros(n, dtype=bool)
            mask[frontier_ids] = True
            cand = dst[mask[src]]
        if len(cand) == 0:
            break
        new = np.unique(cand[depth[cand] < 0])
        if len(new) == 0:
            break
        depth[new] = level
        frontier_ids = new
    return depth
