"""Tests: the topology plane — edge-list vs CSR scan parity across
selectivities and directions, adaptive dispatch, CSR lake materialization
round-trip, incremental invalidation, and the offset-range segment kernel."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.csr import CSRIndex
from repro.core.engine import GraphLakeEngine
from repro.core.topology_plane import DEFAULT_CSR_THRESHOLD
from repro.core.types import VSet
from repro.data.graph500 import generate_graph500, graph500_schema
from repro.data.ldbc import generate_ldbc, ldbc_graph_schema
from repro.kernels import ops as kops, ref
from repro.kernels.csr_expand import csr_segment_sum_pallas
from repro.lakehouse.objectstore import ObjectStore, StoreConfig
from repro.lakehouse.table import LakeCatalog


@pytest.fixture(scope="module")
def g500(tmp_path_factory):
    root = tmp_path_factory.mktemp("lake_plane")
    store = ObjectStore(StoreConfig(root=str(root)))
    schema = generate_graph500(store, scale=8, edge_factor=8, n_files=3,
                               row_group_rows=1024)
    eng = GraphLakeEngine(store, schema)
    eng.startup()
    yield eng
    eng.close()


@pytest.fixture
def ldbc_engine(tmp_path):
    store = ObjectStore(StoreConfig(root=str(tmp_path / "lake")))
    generate_ldbc(store, scale_factor=0.01, n_files=3, row_group_rows=256)
    eng = GraphLakeEngine(store, ldbc_graph_schema(), materialize_topology=False)
    eng.startup()
    yield eng
    eng.close()


def _frontier(n, sel, seed=0):
    rng = np.random.default_rng(seed)
    k = max(1, int(n * sel))
    return VSet.from_dense_ids("Node", n, rng.choice(n, size=k, replace=False))


def _assert_frames_identical(a, b):
    np.testing.assert_array_equal(a.u, b.u)
    np.testing.assert_array_equal(a.v, b.v)
    assert a.columns.keys() == b.columns.keys()
    for k in a.columns:
        np.testing.assert_array_equal(a.columns[k], b.columns[k])


# ---------------------------------------------------------------------------
# edge-list vs CSR scan parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sel", [0.0005, 0.01, 0.2, 1.0])
@pytest.mark.parametrize("direction", ["out", "in"])
def test_edge_scan_parity_across_selectivities(g500, sel, direction):
    n = g500.topology.n_vertices("Node")
    frontier = _frontier(n, sel, seed=int(sel * 10_000))
    el = g500.edge_scan(frontier, "Edge", direction,
                        edge_columns=["weight"], strategy="edgelist")
    cs = g500.edge_scan(frontier, "Edge", direction,
                        edge_columns=["weight"], strategy="csr")
    _assert_frames_identical(el, cs)
    if sel >= 0.01:
        assert len(el) > 0  # scans actually matched something


def test_edge_scan_parity_heterogeneous_types(ldbc_engine):
    """Cross-type edge scan (Comment -HasCreator-> Person), both directions."""
    eng = ldbc_engine
    for direction, vt in (("out", "Comment"), ("in", "Person")):
        n = eng.topology.n_vertices(vt)
        ids = np.arange(0, n, 7, dtype=np.int64)
        frontier = VSet.from_dense_ids(vt, n, ids)
        el = eng.edge_scan(frontier, "HasCreator", direction,
                           edge_columns=["creationDate"], strategy="edgelist")
        cs = eng.edge_scan(frontier, "HasCreator", direction,
                           edge_columns=["creationDate"], strategy="csr")
        _assert_frames_identical(el, cs)
        assert len(el) > 0


def test_edge_scan_parity_with_filter(g500):
    n = g500.topology.n_vertices("Node")
    frontier = _frontier(n, 0.05, seed=3)
    flt = lambda f: f["e.weight"] > 0.5
    el = g500.edge_scan(frontier, "Edge", edge_columns=["weight"],
                        edge_filter=flt, strategy="edgelist")
    cs = g500.edge_scan(frontier, "Edge", edge_columns=["weight"],
                        edge_filter=flt, strategy="csr")
    _assert_frames_identical(el, cs)


def test_edge_scan_empty_frontier(g500):
    n = g500.topology.n_vertices("Node")
    empty = VSet.empty("Node", n)
    for strategy in ("edgelist", "csr", "auto"):
        frame = g500.edge_scan(empty, "Edge", strategy=strategy)
        assert len(frame) == 0


# ---------------------------------------------------------------------------
# CSRIndex structure + serialization
# ---------------------------------------------------------------------------

def test_csr_index_matches_numpy_oracle(g500):
    src, dst = g500.concat_edges("Edge")
    csr = g500.plane.csr("Edge")
    n = g500.topology.n_vertices("Node")
    np.testing.assert_array_equal(csr.degrees("out"), np.bincount(src, minlength=n))
    np.testing.assert_array_equal(csr.degrees("in"), np.bincount(dst, minlength=n))
    v = int(src[0])
    np.testing.assert_array_equal(np.sort(csr.neighbors(v, "out")),
                                  np.sort(dst[src == v]))
    # dst-sorted view is a permutation of the edge set with sorted dst
    s2, d2, eid = csr.edges_by_dst()
    assert np.all(np.diff(d2) >= 0)
    np.testing.assert_array_equal(s2, src[eid])
    np.testing.assert_array_equal(d2, dst[eid])


def test_csr_bytes_roundtrip(g500):
    csr = g500.plane.csr("Edge")
    back = CSRIndex.from_bytes(csr.to_bytes())
    assert back.edge_type == csr.edge_type
    assert (back.n_src, back.n_dst) == (csr.n_src, csr.n_dst)
    for name in ("fwd_indptr", "fwd_dst", "fwd_eid",
                 "rev_indptr", "rev_src", "rev_eid"):
        np.testing.assert_array_equal(getattr(back, name), getattr(csr, name))


def test_csr_survives_second_connection(tmp_path):
    """Materialized topology restores the CSR index — no rebuild."""
    store = ObjectStore(StoreConfig(root=str(tmp_path / "lake")))
    schema = generate_graph500(store, scale=7, edge_factor=8, n_files=2,
                               row_group_rows=1024)
    with GraphLakeEngine(store, schema) as eng1:
        eng1.startup()           # first connection: builds + materializes CSR
        assert eng1.startup_mode == "first_connection"
        assert eng1.plane.csr_ready("Edge")
        csr1 = eng1.plane.csr("Edge")
        n = eng1.topology.n_vertices("Node")
        frontier = _frontier(n, 0.01)
        frame1 = eng1.edge_scan(frontier, "Edge", strategy="csr")

    with GraphLakeEngine(store, schema) as eng2:
        eng2.startup()
        assert eng2.startup_mode == "second_connection"
        assert eng2.plane.csr_ready("Edge")  # restored, not rebuilt
        csr2 = eng2.plane.csr("Edge")
        np.testing.assert_array_equal(csr1.fwd_indptr, csr2.fwd_indptr)
        np.testing.assert_array_equal(csr1.rev_src, csr2.rev_src)
        frame2 = eng2.edge_scan(frontier, "Edge", strategy="csr")
        _assert_frames_identical(frame1, frame2)


# ---------------------------------------------------------------------------
# adaptive dispatch
# ---------------------------------------------------------------------------

def test_adaptive_dispatch_by_selectivity(g500):
    n = g500.topology.n_vertices("Node")
    g500.edge_scan(_frontier(n, 0.001), "Edge", strategy="auto")
    assert g500.plane.last_strategy["Edge"] == "csr"
    g500.edge_scan(g500.all_vertices("Node"), "Edge", strategy="auto")
    assert g500.plane.last_strategy["Edge"] == "edgelist"


def test_adaptive_threshold_override(g500, monkeypatch):
    n = g500.topology.n_vertices("Node")
    small = _frontier(n, 0.001)
    # threshold 0 -> nothing is "low selectivity" -> edge lists always
    monkeypatch.setenv("REPRO_OPTS", "csr=0.0")
    g500.edge_scan(small, "Edge", strategy="auto")
    assert g500.plane.last_strategy["Edge"] == "edgelist"
    # threshold 1.0 -> every frontier qualifies for CSR
    monkeypatch.setenv("REPRO_OPTS", "csr=1.0")
    g500.edge_scan(g500.all_vertices("Node"), "Edge", strategy="auto")
    assert g500.plane.last_strategy["Edge"] == "csr"
    assert g500.plane.threshold() == 1.0


def test_csr_flag_disables_dispatch(g500, monkeypatch):
    n = g500.topology.n_vertices("Node")
    monkeypatch.setenv("REPRO_OPTS", "")  # baseline: all perf flags off
    g500.edge_scan(_frontier(n, 0.001), "Edge", strategy="auto")
    assert g500.plane.last_strategy["Edge"] == "edgelist"
    assert g500.plane.threshold() == DEFAULT_CSR_THRESHOLD


# ---------------------------------------------------------------------------
# invalidation on incremental refresh
# ---------------------------------------------------------------------------

def test_refresh_invalidates_plane(ldbc_engine):
    eng = ldbc_engine
    topo = eng.topology
    before_edges = topo.n_edges("Knows")
    eng.plane.csr("Knows")
    src0, _ = eng.concat_edges("Knows")
    assert eng.plane.csr_ready("Knows")

    lake = LakeCatalog(eng.store)
    t = lake.table("Person_Knows_Person")
    person_raw = topo.idm.raw_ids("Person")
    t.append_files([{
        "src": person_raw[:10],
        "dst": person_raw[10:20],
        "creationDate": np.full(10, 20230101, dtype=np.int64),
    }])
    added, removed = topo.refresh_edges(eng.store, lake, "Knows")
    assert (added, removed) == (1, 0)
    assert not eng.plane.csr_ready("Knows")      # CSR dropped
    src1, _ = eng.concat_edges("Knows")          # concat cache rebuilt
    assert len(src1) == len(src0) + 10
    assert eng.plane.csr("Knows").n_edges == before_edges + 10

    # parity still holds on the refreshed topology
    n = topo.n_vertices("Person")
    frontier = VSet.from_dense_ids("Person", n, np.arange(0, n, 3))
    el = eng.edge_scan(frontier, "Knows", strategy="edgelist")
    cs = eng.edge_scan(frontier, "Knows", strategy="csr")
    _assert_frames_identical(el, cs)


def test_concat_edges_cached_until_invalidated(g500):
    a = g500.concat_edges("Edge")
    b = g500.concat_edges("Edge")
    assert a[0] is b[0] and a[1] is b[1]


# ---------------------------------------------------------------------------
# offset-range segment kernel (CSR frontier-expand path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,n,d", [(64, 16, 8), (1000, 100, 16), (4096, 512, 128),
                                   (100, 1000, 4), (1, 1, 8)])
def test_csr_segment_sum_kernel_matches_ref(e, n, d):
    rng = np.random.default_rng(e + n + d)
    dst = np.sort(rng.integers(0, n, size=e))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(dst, minlength=n), out=indptr[1:])
    values = jnp.asarray(rng.standard_normal((e, d)), dtype=jnp.float32)
    got = csr_segment_sum_pallas(values, jnp.asarray(indptr), n, interpret=True)
    want = ref.csr_segment_sum(values, jnp.asarray(indptr), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_csr_segment_sum_matches_edge_segment_sum(g500):
    """The CSR offset-range reduction equals the scattered-id reduction."""
    csr = g500.plane.csr("Edge")
    n = g500.topology.n_vertices("Node")
    src, dst = g500.edges_by_dst("Edge")
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.standard_normal((len(src), 4)), dtype=jnp.float32)
    a = kops.csr_segment_sum(vals, jnp.asarray(csr.rev_indptr), n)
    b = ref.edge_segment_sum(vals, jnp.asarray(dst, dtype=jnp.int32), n)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4)


def test_csr_segment_sum_1d(g500):
    csr = g500.plane.csr("Edge")
    n = g500.topology.n_vertices("Node")
    vals = jnp.ones(csr.n_edges, dtype=jnp.float32)
    got = kops.csr_segment_sum(vals, jnp.asarray(csr.rev_indptr), n)
    np.testing.assert_allclose(np.asarray(got), csr.degrees("in").astype(np.float32))


def _indptr_case(name: str):
    """(indptr, e) for one shape of CSR the linear id derivation must get right."""
    rng = np.random.default_rng(len(name))
    if name == "empty_segments":
        deg = rng.integers(0, 4, size=60) * (rng.random(60) < 0.4)
        return np.concatenate([[0], np.cumsum(deg)]), int(deg.sum())
    if name == "one_giant_segment":
        return np.array([0] * 8 + [1000] * 9), 1000
    if name == "n_is_1":
        return np.array([0, 37]), 37
    if name == "e_is_1":
        return np.array([0, 0, 0, 0, 1, 1, 1, 1, 1]), 1
    if name == "e_below_n":
        return np.concatenate([[0], np.sort(rng.integers(0, 21, size=100))]), 20
    if name == "arcs_past_last_offset":
        return np.concatenate([[0], np.sort(rng.integers(0, 61, size=9)), [60]]), 80
    assert name == "arcs_before_first_offset"
    return np.concatenate([[5], np.sort(rng.integers(5, 40, size=9)), [40]]), 40


_INDPTR_CASES = ["empty_segments", "one_giant_segment", "n_is_1", "e_is_1", "e_below_n",
                 "arcs_past_last_offset", "arcs_before_first_offset"]


@pytest.mark.parametrize("case", _INDPTR_CASES)
def test_csr_segment_ids_match_searchsorted(case):
    indptr, e = _indptr_case(case)
    got = kops._csr_segment_ids(jnp.asarray(indptr, jnp.int32), e)
    want = np.searchsorted(indptr, np.arange(e), side="right") - 1
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("d", [None, 4])
@pytest.mark.parametrize("case", _INDPTR_CASES)
def test_csr_segment_sum_off_pallas_matches_ref(case, d, monkeypatch):
    """The non-Pallas path of ``csr_segment_sum`` (1-D always, 2-D off the
    chip) against the searchsorted reference."""
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    assert not kops.use_pallas()
    indptr, e = _indptr_case(case)
    n = len(indptr) - 1
    rng = np.random.default_rng(e + n)
    shape = (e,) if d is None else (e, d)
    values = jnp.asarray(rng.standard_normal(shape), dtype=jnp.float32)
    indptr = jnp.asarray(indptr, jnp.int32)
    got = kops.csr_segment_sum(values, indptr, n)
    want = ref.csr_segment_sum(values, indptr, n)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_csr_segment_sum_1d_compiles_without_a_loop():
    """The 1-D path derives its segment ids in one pass: a binary search,
    which compiles to a ``while`` loop over every arc, must not come back."""
    e, n = 2**20, 2**14
    text = jax.jit(lambda v, p: kops.csr_segment_sum(v, p, n)).lower(
        jax.ShapeDtypeStruct((e,), jnp.float32),
        jax.ShapeDtypeStruct((n + 1,), jnp.int32)).compile().as_text()
    assert not re.search(r" while\(", text)
