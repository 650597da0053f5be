"""Plain numpy references of the BI templates, over the generated columns.

Independent of the program's executor, GSQL front end and lookup path: each
template is written out by hand as masks and ``bincount``s over the edge
tables the generator made.  The semantics are those of the engine's hop
executor:

- a statement starts from its seed vertices (all of a type, filtered by the
  WHERE conjuncts on the seed alias, or by an accumulator of an earlier
  statement);
- each hop scans every edge of its type whose near end is in the current
  vertex set (duplicates included) and keeps those that pass the edge and
  far-end predicates; the next set is the distinct far ends;
- ``ACCUM x.@a += v`` adds ``v`` once per kept edge of the hop to the vertex
  ``x`` at one of its ends;
- ``SELECT`` of the seed alias gives the seed vertices that kept an edge in
  the first hop; of a later alias, the set its hop reached;
- a POST-ACCUM block scans from the selected set after the main hops;
- the edge count is the kept edges of every hop and block.

An answer is a dict in raw-id space: ``vset`` and ``aliases`` as
``(vertex type, sorted raw ids)``, ``accums`` as ``(vertex type, values in
raw-id order)``, ``edges`` as an int.  ``dtype`` is the accumulators' float:
``float64`` is the reference; ``float32`` is the control.
"""

from __future__ import annotations

import numpy as np


class LDBCReference:
    def __init__(self, tables: dict, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        P, C, T = tables["Person"], tables["Comment"], tables["Tag"]
        self.ids = {"Person": P["id"], "Comment": C["id"], "Tag": T["id"]}
        self.n = {k: len(v) for k, v in self.ids.items()}
        idx = lambda vt, raw: np.searchsorted(self.ids[vt], raw)  # noqa: E731
        k = tables["Person_Knows_Person"]
        self.k_src, self.k_dst = idx("Person", k["src"]), idx("Person", k["dst"])
        hc = tables["Comment_HasCreator_Person"]
        self.hc_src, self.hc_dst = idx("Comment", hc["src"]), idx("Person", hc["dst"])
        self.hc_date = hc["creationDate"]
        ht = tables["Comment_HasTag_Tag"]
        self.ht_src, self.ht_dst = idx("Comment", ht["src"]), idx("Tag", ht["dst"])
        self.female = P["gender"] == "Female"
        self.city = P["locationCity"]
        self.length = C["length"]
        self.tag_name = T["name"]

    # -- helpers ------------------------------------------------------------------

    def _mask(self, vt: str, members: np.ndarray) -> np.ndarray:
        m = np.zeros(self.n[vt], dtype=bool)
        m[members] = True
        return m

    def _set(self, vt: str, members: np.ndarray):
        return vt, self.ids[vt][np.unique(members)]

    def _sum(self, vt: str, at: np.ndarray, values=None) -> np.ndarray:
        """Per-vertex sum in ``self.dtype``, one edge at a time."""
        out = np.zeros(self.n[vt], dtype=self.dtype)
        vals = (np.ones(len(at), self.dtype) if values is None
                else np.asarray(values).astype(self.dtype))
        if self.dtype == np.float64:
            # exact: every value is an integer and every sum is below 2**53
            out += np.bincount(at, weights=vals, minlength=self.n[vt])
        else:
            np.add.at(out, at, vals)
        return out.astype(np.float64)

    # -- the templates ------------------------------------------------------------

    def bi1(self, tag, date):
        t = np.flatnonzero(self.tag_name == tag)
        m1 = self._mask("Tag", t)[self.ht_dst]
        c = np.unique(self.ht_src[m1])
        m2 = (self._mask("Comment", c)[self.hc_src] & (self.hc_date > date)
              & self.female[self.hc_dst])
        p = self.hc_dst[m2]
        return {"vset": self._set("Person", p),
                "aliases": {"t": self._set("Tag", t), "c": self._set("Comment", c),
                            "p": self._set("Person", p)},
                "accums": {"cnt": ("Person", self._sum("Person", p))},
                "edges": int(m1.sum() + m2.sum())}

    def bi2(self, lo, hi):
        m1 = (self.hc_date >= lo) & (self.hc_date <= hi)
        active = np.unique(self.hc_src[m1])
        m2 = self._mask("Comment", active)[self.ht_src]
        t = self.ht_dst[m2]
        return {"vset": self._set("Comment", active),
                "aliases": {"c": self._set("Comment", np.arange(self.n["Comment"])),
                            "p": self._set("Person", self.hc_dst[m1]),
                            "t": self._set("Tag", t)},
                "accums": {"tag_cnt": ("Tag", self._sum("Tag", t))},
                "edges": int(m1.sum() + m2.sum())}

    def bi3(self, min_len):
        c = np.flatnonzero(self.length > min_len)
        m1 = self._mask("Comment", c)[self.hc_src]
        p = self.hc_dst[m1]
        return {"vset": self._set("Person", p),
                "aliases": {"c": self._set("Comment", c), "p": self._set("Person", p)},
                "accums": {"tot_len": ("Person", self._sum(
                    "Person", p, self.length[self.hc_src[m1]]))},
                "edges": int(m1.sum())}

    def bi4(self, city):
        s = np.flatnonzero(self.city == city)
        m1 = self._mask("Person", s)[self.k_src]
        return {"vset": self._set("Person", self.k_src[m1]),
                "aliases": {"s": self._set("Person", s),
                            "q": self._set("Person", self.k_dst[m1])},
                "accums": {"deg": ("Person", self._sum("Person", self.k_src[m1]))},
                "edges": int(m1.sum())}

    def bi5(self, min_degree, date):
        deg = self._sum("Person", self.k_src)
        s = np.flatnonzero(deg >= min_degree)
        m1 = self._mask("Person", s)[self.hc_dst] & (self.hc_date > date)
        c = np.unique(self.hc_src[m1])
        m2 = self._mask("Comment", c)[self.ht_src]
        t = self.ht_dst[m2]
        return {"vset": self._set("Tag", t),
                "aliases": {"a": self._set("Person", np.arange(self.n["Person"])),
                            "q": self._set("Person", self.k_dst),
                            "s": self._set("Person", s), "c": self._set("Comment", c),
                            "t": self._set("Tag", t)},
                "accums": {"deg": ("Person", deg),
                           "inf_cnt": ("Tag", self._sum("Tag", t))},
                "edges": int(len(self.k_src) + m1.sum() + m2.sum())}

    def answer(self, template: str, params: dict) -> dict:
        return getattr(self, template)(**params)


def reference(tables: dict, dtype=np.float64) -> LDBCReference:
    """The open-loop driver's entry: the reference over ``tables``."""
    return LDBCReference(tables, dtype)


def compact(result):
    """What the check needs of one answer, kept from the window until the
    check: the ``QueryResult`` without its frames."""
    return result.__class__(vset=result.vset, accumulators=result.accumulators,
                            n_edges_scanned=result.n_edges_scanned, frames=[],
                            alias_sets=result.alias_sets)


def program_answer(result, raw_of_dense: dict, ref: dict) -> dict:
    """An answer as ``compact`` kept it, in the reference's raw-id form; the
    vertex type of each accumulator is the reference's for that name."""
    def as_set(vs):
        return vs.vertex_type, np.sort(raw_of_dense[vs.vertex_type][vs.ids()])

    accums = {}
    for name, arr in result.accumulators.items():
        vt = ref["accums"][name][0] if name in ref["accums"] else None
        raw = raw_of_dense.get(vt)
        if raw is None or len(raw) != len(arr):
            accums[name] = (vt, np.asarray(arr))
            continue
        accums[name] = (vt, np.asarray(arr, np.float64)[np.argsort(raw)])
    return {"vset": as_set(result.vset),
            "aliases": {k: as_set(v) for k, v in result.alias_sets.items()},
            "accums": accums, "edges": int(result.n_edges_scanned)}


def same_answer(a: dict, b: dict) -> bool:
    def same_set(x, y):
        return x[0] == y[0] and np.array_equal(x[1], y[1])

    return (a["edges"] == b["edges"] and same_set(a["vset"], b["vset"])
            and a["aliases"].keys() == b["aliases"].keys()
            and all(same_set(a["aliases"][k], b["aliases"][k]) for k in a["aliases"])
            and a["accums"].keys() == b["accums"].keys()
            and all(a["accums"][k][0] == b["accums"][k][0]
                    and np.array_equal(a["accums"][k][1], b["accums"][k][1])
                    for k in a["accums"]))
