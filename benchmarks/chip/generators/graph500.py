"""Graph500 Kronecker graph from a seed, made on the device, as LDBC
Graphalytics prepares it: undirected, deduplicated, relabelled.

The law of the Graph500 generator (A/B/C per bit, ``edge_factor * 2**scale``
edges drawn) with its seeded permutation of the vertex labels; then, as
Graphalytics' Graph500 data sets hold it, self-loops and duplicate edges are
dropped and only vertices with an edge are kept.  The engine's PageRank is
directed, so each undirected edge is stored as two arcs, one each way,
which is how Graphalytics runs an undirected graph.  The layout is the
repo's ``data/graph500.py``: ``Node(id)``, ``Node_Edge_Node(src, dst,
weight)`` sorted by src, row groups of ``row_group_rows``; both arcs of an
edge carry its one weight.  Everything is drawn and sorted in one jitted
call of fixed shapes; ``generate`` returns host numpy columns (the plain
reference reads these) and ``write`` puts them in the lake through the
program's writer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int):
    # any whole number, wider than 32 bits too, folds into one 31-bit key
    word = int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF
    return jax.random.key(word)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor"))
def _graph(key, a, b, c, scale: int, edge_factor: int):
    n, n_e = 1 << scale, edge_factor << scale
    ab, abc = a + b, a + b + c
    k_bits, k_perm, k_weight = jax.random.split(key, 3)

    def bit(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(k_bits, i), (n_e,))
        down = r >= ab                                  # quadrants C, D
        right = ((r >= a) & (r < ab)) | (r >= abc)      # quadrants B, D
        return (src | (down.astype(jnp.int32) << i),
                dst | (right.astype(jnp.int32) << i))

    zero = jnp.zeros(n_e, jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    weight = jax.random.uniform(k_weight, (n_e,))
    # one entry per undirected edge, (lo, hi) sorted; the first of equals stays
    lo, hi, weight = jax.lax.sort((jnp.minimum(src, dst), jnp.maximum(src, dst), weight),
                                  num_keys=2)
    new = jnp.concatenate([jnp.ones(1, bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = new & (lo != hi)
    # two arcs per kept edge; dropped ones take the id n and sort last
    arc_src = jnp.concatenate([jnp.where(keep, lo, n), jnp.where(keep, hi, n)])
    arc_dst = jnp.concatenate([hi, lo])
    arc_src, arc_dst, arc_w = jax.lax.sort((arc_src, arc_dst, jnp.concatenate([weight, weight])),
                                           num_keys=2)
    has_edge = jnp.zeros(n + 1, bool).at[arc_src].set(True)[:n]
    return arc_src, arc_dst, arc_w, 2 * keep.sum(), has_edge


def generate(cfg: dict, seed: int) -> dict:
    src, dst, weight, m, has_edge = jax.device_get(
        _graph(_key(seed), cfg["a"], cfg["b"], cfg["c"], scale=cfg["scale"],
               edge_factor=cfg["edge_factor"]))
    m = int(m)
    return {"Node": {"id": np.flatnonzero(has_edge).astype(np.int64)},
            "Node_Edge_Node": {"src": src[:m].astype(np.int64),
                               "dst": dst[:m].astype(np.int64),
                               "weight": weight[:m].astype(np.float64)}}


def graph_schema():
    from repro.core.types import GraphSchema

    g = GraphSchema()
    g.add_vertex_type("Node", table="Node", primary_key="id")
    g.add_edge_type("Edge", table="Node_Edge_Node", src_type="Node",
                    dst_type="Node", src_column="src", dst_column="dst")
    return g


def write(tables: dict, store, cfg: dict) -> None:
    from repro.lakehouse.table import ColumnSpec, TableSchema
    from repro.lakehouse.writer import write_table

    write_table(store, TableSchema("Node", [ColumnSpec("id", "int64",
                                                       role="primary_key")]),
                tables["Node"], n_files=max(1, cfg["n_files"] // 2),
                row_group_rows=cfg["row_group_rows"])
    write_table(store, TableSchema("Node_Edge_Node", [
        ColumnSpec("src", "int64", role="foreign_key"),
        ColumnSpec("dst", "int64", role="foreign_key"),
        ColumnSpec("weight", "float64"),
    ]), tables["Node_Edge_Node"], n_files=cfg["n_files"],
        row_group_rows=cfg["row_group_rows"])
