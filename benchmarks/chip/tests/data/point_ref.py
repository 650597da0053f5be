"""A plain reference of two point-read templates over the LDBC tables, for
the test of a cell that the server serves through the point-lookup tier.

``point`` is the person with raw id ``pid``; ``knows`` takes one Knows hop
from it and adds 1 to ``@cnt`` at the far end of each edge.  An answer is a
dict in raw-id space, as in ``ldbc_queries``, with one more key: ``route``,
which ``compact`` keeps and ``ldbc_queries.compact`` drops.  ``same_answer``
holds every answer to the lookup route, and ``ROUTES`` records the route of
each answer ``program_answer`` was given.
"""

from __future__ import annotations

import numpy as np

ROUTES: list = []


class PointReference:
    def __init__(self, tables: dict, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        self.ids = tables["Person"]["id"]
        k = tables["Person_Knows_Person"]
        self.src = np.searchsorted(self.ids, k["src"])
        self.dst = np.searchsorted(self.ids, k["dst"])

    def answer(self, template: str, params: dict) -> dict:
        seed = np.flatnonzero(self.ids == params["pid"])
        p = ("Person", self.ids[seed])
        if template == "point":
            return {"vset": p, "aliases": {"p": p}, "accums": {}, "edges": 0,
                    "route": "lookup"}
        kept = np.isin(self.src, seed)
        cnt = np.zeros(len(self.ids), self.dtype)
        np.add.at(cnt, self.dst[kept], 1)
        q = ("Person", self.ids[np.unique(self.dst[kept])])
        return {"vset": q, "aliases": {"p": p, "q": q},
                "accums": {"cnt": ("Person", cnt.astype(np.float64))},
                "edges": int(kept.sum()), "route": "lookup"}


def reference(tables: dict, dtype=np.float64) -> PointReference:
    return PointReference(tables, dtype)


def compact(result) -> dict:
    return {"vset": result.vset, "aliases": result.alias_sets,
            "accums": result.accumulators, "edges": result.n_edges_scanned,
            "route": result.route}


def program_answer(kept: dict, raw_of_dense: dict, ref: dict) -> dict:
    ROUTES.append(kept["route"])

    def as_set(vs):
        return vs.vertex_type, np.sort(raw_of_dense[vs.vertex_type][vs.ids()])

    raw = raw_of_dense["Person"]
    return {"vset": as_set(kept["vset"]),
            "aliases": {k: as_set(v) for k, v in kept["aliases"].items()},
            "accums": {k: ("Person", np.asarray(v, np.float64)[np.argsort(raw)])
                       for k, v in kept["accums"].items()},
            "edges": int(kept["edges"]), "route": kept["route"]}


def same_answer(a: dict, b: dict) -> bool:
    def same_set(x, y):
        return x[0] == y[0] and np.array_equal(x[1], y[1])

    return (a["route"] == b["route"] and a["edges"] == b["edges"]
            and same_set(a["vset"], b["vset"])
            and a["aliases"].keys() == b["aliases"].keys()
            and all(same_set(a["aliases"][k], b["aliases"][k]) for k in a["aliases"])
            and a["accums"].keys() == b["accums"].keys()
            and all(np.array_equal(a["accums"][k][1], b["accums"][k][1])
                    for k in a["accums"]))
