"""Plain PageRank references over an edge list (``src -> dst``).

Semantics of the engine's ``algorithms.pagerank``: ranks start uniform at
``1/n``; each superstep sends ``rank / out_degree`` along every edge
(duplicates count), spreads the rank of vertices with no out-edge uniformly,
and applies damping.  ``float64`` is the reference (copied from the power
iteration in ``chip_smoke.py``); ``lower_precision`` is the same iteration in
a narrower float on the device, the control that a correct run has to beat.
"""

from __future__ import annotations

import numpy as np


def pagerank_f64(src: np.ndarray, dst: np.ndarray, n: int, damping: float,
                 supersteps: int) -> np.ndarray:
    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    inv_deg = 1.0 / np.maximum(out_deg, 1.0)
    dangling = out_deg == 0
    rank = np.full(n, 1.0 / n)
    for _ in range(supersteps):
        agg = np.bincount(dst, weights=(rank * inv_deg)[src], minlength=n)
        rank = (1.0 - damping) / n + damping * (agg + rank[dangling].sum() / n)
    return rank


def pagerank_tables(tables: dict, edge_table: str, vertex_table: str,
                    damping: float, supersteps: int) -> np.ndarray:
    """``pagerank_f64`` over generated tables: vertices in the order of
    their raw ids, edges given as raw ids in ``src``/``dst``."""
    ids = tables[vertex_table]["id"]
    edges = tables[edge_table]
    return pagerank_f64(np.searchsorted(ids, edges["src"]),
                        np.searchsorted(ids, edges["dst"]), len(ids), damping,
                        supersteps)


def lower_precision(src: np.ndarray, dst: np.ndarray, n: int, damping: float,
                    supersteps: int, dtype: str = "bfloat16") -> np.ndarray:
    """The same iteration with every rank, contribution and sum held in
    ``dtype``; out-degrees stay exact integers."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    s, d = jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)

    @jax.jit
    def run(s, d):
        out_deg = jax.ops.segment_sum(jnp.ones(s.shape, jnp.int32), s,
                                      num_segments=n)
        inv_deg = (1.0 / jnp.maximum(out_deg, 1)).astype(dt)
        dangling = out_deg == 0
        rank = jnp.full(n, 1.0 / n, dt)
        for _ in range(supersteps):
            agg = jax.ops.segment_sum((rank * inv_deg)[s], d, num_segments=n)
            spread = jnp.where(dangling, rank, 0).sum(dtype=dt) / n
            rank = ((1.0 - damping) / n + damping * (agg + spread)).astype(dt)
        return rank

    return np.asarray(jax.device_get(run(s, d)).astype(np.float64))


def max_rel_err(ranks: np.ndarray, ref: np.ndarray) -> float:
    """Largest ``|rank - ref| / ref`` over all vertices (``ref > 0``)."""
    return float(np.max(np.abs(np.asarray(ranks, np.float64) - ref) / ref))
