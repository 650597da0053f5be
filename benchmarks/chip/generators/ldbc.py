"""LDBC SNB-style social network from a seed.

A copy of the law and layout of the repo's ``data/ldbc.py``, so that the
yardstick does not move when the program's generator does.  ``generate``
returns the tables as numpy columns (the plain references read these);
``write`` puts them in the lake through the program's table writer.

Tables (raw ids are sparse: ``10 * i + 1`` persons, ``+ 3`` comments,
``+ 7`` tags)::

    Person(id, firstName, gender, birthday, locationCity)
    Comment(id, creationDate, length, browserUsed)
    Tag(id, name)
    Person_Knows_Person(src, dst, creationDate)      sorted by src
    Comment_HasCreator_Person(src, dst, creationDate) sorted by src
    Comment_HasTag_Tag(src, dst)                     sorted by src
"""

from __future__ import annotations

import numpy as np

TAG_NAMES = ["Music", "Sports", "Politics", "Movies", "Science", "Travel", "Food",
             "Art", "History", "Fashion", "Gaming", "Books", "Nature", "Tech", "Cars"]
BROWSERS = ["Chrome", "Firefox", "Safari", "Edge"]
CITIES = [f"city_{i}" for i in range(50)]


def _zipf_targets(rng, n_draws: int, n_targets: int, alpha: float) -> np.ndarray:
    ranks = rng.zipf(alpha, size=n_draws).astype(np.int64)
    return (ranks - 1) % max(n_targets, 1)


def generate(cfg: dict, seed: int) -> dict:
    """{table: {column: array}} for one seed; edge tables sorted by src."""
    rng = np.random.default_rng(seed)
    n_p, n_c, n_t = cfg["persons"], cfg["comments"], cfg["tags"]
    alpha = cfg["zipf_alpha"]

    person_ids = np.arange(1, n_p + 1, dtype=np.int64) * 10 + 1
    persons = {
        "id": person_ids,
        "firstName": np.array([f"name_{i % 997}" for i in range(n_p)], dtype=object),
        "gender": np.array(rng.choice(["Female", "Male"], size=n_p), dtype=object),
        "birthday": rng.integers(19400101, 20051231, size=n_p).astype(np.int64),
        "locationCity": np.array(rng.choice(CITIES, size=n_p), dtype=object),
    }
    comment_ids = np.arange(1, n_c + 1, dtype=np.int64) * 10 + 3
    # creation dates trend with the row order, with jitter, as in an event table
    date_base = np.linspace(20080101, 20221231, n_c)
    date_jitter = rng.integers(-5000, 5001, size=n_c)
    comments = {
        "id": comment_ids,
        "creationDate": np.clip(date_base + date_jitter, 20080101,
                                20221231).astype(np.int64),
        "length": rng.integers(1, 2000, size=n_c).astype(np.int64),
        "browserUsed": np.array(rng.choice(BROWSERS, size=n_c), dtype=object),
    }
    tag_ids = np.arange(1, n_t + 1, dtype=np.int64) * 10 + 7
    tags = {
        "id": tag_ids,
        "name": np.array([TAG_NAMES[i % len(TAG_NAMES)]
                          + ("" if i < len(TAG_NAMES) else f"_{i}")
                          for i in range(n_t)], dtype=object),
    }

    def by_src(cols: dict) -> dict:
        order = np.argsort(cols["src"], kind="stable")
        return {k: v[order] for k, v in cols.items()}

    n_knows = n_p * cfg["knows_per_person"]
    k_src = person_ids[rng.integers(0, n_p, size=n_knows)]
    k_dst = person_ids[_zipf_targets(rng, n_knows, n_p, alpha)]
    keep = k_src != k_dst
    knows = by_src({
        "src": k_src[keep], "dst": k_dst[keep],
        "creationDate": rng.integers(20080101, 20221231,
                                     size=int(keep.sum())).astype(np.int64)})
    has_creator = by_src({
        "src": comment_ids,
        "dst": person_ids[_zipf_targets(rng, n_c, n_p, alpha)],
        "creationDate": comments["creationDate"]})
    n_ht = n_c * cfg["tags_per_comment"]
    has_tag = by_src({
        "src": comment_ids[rng.integers(0, n_c, size=n_ht)],
        "dst": tag_ids[_zipf_targets(rng, n_ht, n_t, alpha)]})
    return {"Person": persons, "Comment": comments, "Tag": tags,
            "Person_Knows_Person": knows,
            "Comment_HasCreator_Person": has_creator,
            "Comment_HasTag_Tag": has_tag}


def graph_schema():
    from repro.core.types import GraphSchema

    g = GraphSchema()
    for v in ("Person", "Comment", "Tag"):
        g.add_vertex_type(v, table=v, primary_key="id")
    g.add_edge_type("Knows", table="Person_Knows_Person", src_type="Person",
                    dst_type="Person", src_column="src", dst_column="dst")
    g.add_edge_type("HasCreator", table="Comment_HasCreator_Person",
                    src_type="Comment", dst_type="Person",
                    src_column="src", dst_column="dst")
    g.add_edge_type("HasTag", table="Comment_HasTag_Tag", src_type="Comment",
                    dst_type="Tag", src_column="src", dst_column="dst")
    return g


def _dtype(arr: np.ndarray) -> str:
    return "str" if arr.dtype == object else str(arr.dtype)


def write(tables: dict, store, cfg: dict) -> None:
    """Write every table through the program's lake writer."""
    from repro.lakehouse.table import ColumnSpec, TableSchema
    from repro.lakehouse.writer import write_table

    for name, cols in tables.items():
        is_edge = "src" in cols
        specs = [ColumnSpec(col, _dtype(arr), role=(
            "primary_key" if col == "id" else
            "foreign_key" if is_edge and col in ("src", "dst") else "property"))
            for col, arr in cols.items()]
        n_files = cfg["n_files"] if name != "Tag" else max(1, cfg["n_files"] // 2)
        write_table(store, TableSchema(name, specs), cols, n_files=n_files,
                    row_group_rows=cfg["row_group_rows"])

