"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.edge_scan import edge_segment_sum_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# edge_scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,n,d", [(64, 16, 8), (1000, 100, 16), (4096, 512, 128),
                                   (100, 1000, 4), (1, 1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_edge_segment_sum_shapes(e, n, d, dtype):
    rng = _rng(e + n + d)
    values = jnp.asarray(rng.standard_normal((e, d)), dtype=dtype)
    dst = jnp.asarray(rng.integers(0, n, size=e), dtype=jnp.int32)
    got = edge_segment_sum_pallas(values, dst, n, block_e=128, block_n=64,
                                  interpret=True)
    want = ref.edge_segment_sum(values.astype(jnp.float32), dst, n)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


def test_edge_segment_sum_sorted_input():
    """Sorted dst (the paper's sorted-FK layout) must give exact results too."""
    rng = _rng(5)
    e, n, d = 2048, 256, 32
    dst = jnp.asarray(np.sort(rng.integers(0, n, size=e)), dtype=jnp.int32)
    values = jnp.asarray(rng.standard_normal((e, d)), dtype=jnp.float32)
    got = edge_segment_sum_pallas(values, dst, n, block_e=256, block_n=64,
                                  interpret=True)
    want = ref.edge_segment_sum(values, dst, n)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(
    st.integers(min_value=1, max_value=300),
    st.integers(min_value=1, max_value=50),
    st.integers(min_value=1, max_value=16),
)
def test_edge_segment_sum_property(e, n, d):
    rng = _rng(e * 31 + n * 7 + d)
    values = jnp.asarray(rng.standard_normal((e, d)), dtype=jnp.float32)
    dst = jnp.asarray(rng.integers(0, n, size=e), dtype=jnp.int32)
    got = edge_segment_sum_pallas(values, dst, n, block_e=64, block_n=32,
                                  interpret=True)
    want = ref.edge_segment_sum(values, dst, n)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # conservation: total mass preserved
    np.testing.assert_allclose(np.asarray(got).sum(), np.asarray(values).sum(),
                               rtol=1e-3, atol=1e-3)


def test_masked_edge_segment_sum_frontier_semantics():
    rng = _rng(9)
    e, n, d = 512, 64, 8
    src = jnp.asarray(rng.integers(0, n, size=e), dtype=jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, size=e), dtype=jnp.int32)
    values = jnp.asarray(rng.standard_normal((e, d)), dtype=jnp.float32)
    frontier = jnp.asarray(rng.random(n) < 0.3)
    got = ref.masked_edge_segment_sum(values, src, dst, frontier, n)
    mask = np.asarray(frontier)[np.asarray(src)]
    want = ref.edge_segment_sum(values * mask[:, None].astype(np.float32), dst, n)
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# embedding_bag kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v,d,b,l", [(100, 8, 32, 4), (1000, 16, 64, 8),
                                     (512, 128, 256, 2), (50, 10, 7, 39)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_shapes(v, d, b, l, dtype):
    rng = _rng(v + d + b + l)
    table = jnp.asarray(rng.standard_normal((v, d)), dtype=dtype)
    idx = jnp.asarray(rng.integers(0, v, size=(b, l)), dtype=jnp.int32)
    w = jnp.asarray((rng.random((b, l)) < 0.8).astype(np.float32))
    got = embedding_bag_pallas(table, idx, w, block_b=64, block_v=128,
                               interpret=True)
    want = ref.embedding_bag(table.astype(jnp.float32), idx, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(
    st.integers(min_value=2, max_value=200),
    st.integers(min_value=1, max_value=12),
    st.integers(min_value=1, max_value=40),
)
def test_embedding_bag_property(v, l, b):
    rng = _rng(v * 13 + l * 5 + b)
    d = 8
    table = jnp.asarray(rng.standard_normal((v, d)), dtype=jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, size=(b, l)), dtype=jnp.int32)
    w = jnp.asarray(rng.random((b, l)).astype(np.float32))
    got = embedding_bag_pallas(table, idx, w, block_b=32, block_v=64, interpret=True)
    want = ref.embedding_bag(table, idx, w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_embedding_bag_padding_weights_zero():
    """Weight-0 (padding) entries must not contribute even with index -1."""
    table = jnp.asarray(np.eye(8, dtype=np.float32))
    idx = jnp.asarray([[0, -1], [3, -1]], dtype=jnp.int32)
    w = jnp.asarray([[1.0, 0.0], [2.0, 0.0]], dtype=jnp.float32)
    got = embedding_bag_pallas(table, idx, w, block_b=8, block_v=8, interpret=True)
    want = np.zeros((2, 8), dtype=np.float32)
    want[0, 0] = 1.0
    want[1, 3] = 2.0
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,dh", [(1, 2, 128, 32), (2, 4, 256, 64), (1, 1, 64, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_naive(b, h, s, dh, causal):
    rng = _rng(b * h + s + dh)
    q = jnp.asarray(rng.standard_normal((b, h, s, dh)), dtype=jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((b, h, s, dh)), dtype=jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((b, h, s, dh)), dtype=jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=64, block_kv=64,
                                 interpret=True)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_decode_alignment():
    """q shorter than kv (decode/chunked prefill): causal offset aligns to the
    kv tail."""
    rng = _rng(77)
    b, h, dh = 1, 2, 32
    q = jnp.asarray(rng.standard_normal((b, h, 64, dh)), dtype=jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((b, h, 256, dh)), dtype=jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((b, h, 256, dh)), dtype=jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_kv=64,
                                 interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_blockwise_ref_matches_naive():
    """The CPU dry-run attention path must match the naive oracle too."""
    rng = _rng(11)
    for (b, h, s, dh, causal) in [(2, 2, 96, 32, True), (1, 4, 200, 64, False)]:
        q = jnp.asarray(rng.standard_normal((b, h, s, dh)), dtype=jnp.float32) * 0.4
        k = jnp.asarray(rng.standard_normal((b, h, s, dh)), dtype=jnp.float32) * 0.4
        v = jnp.asarray(rng.standard_normal((b, h, s, dh)), dtype=jnp.float32)
        got = ref.attention_blockwise(q, k, v, causal=causal, block_kv=64)
        want = ref.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_bf16_attention_tolerance():
    rng = _rng(13)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), dtype=jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_kv=64,
                                 interpret=True)
    want = ref.attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------

def test_ops_dispatch_interpret(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    rng = _rng(1)
    values = jnp.asarray(rng.standard_normal((256, 16)), dtype=jnp.float32)
    dst = jnp.asarray(rng.integers(0, 32, size=256), dtype=jnp.int32)
    got = ops.edge_segment_sum(values, dst, 32)
    want = ref.edge_segment_sum(values, dst, 32)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    monkeypatch.setenv("REPRO_PALLAS", "off")
    got2 = ops.edge_segment_sum(values, dst, 32)
    np.testing.assert_allclose(got2, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# stacked segment sum + pytree stacking (shared-scan batch layout)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,e,n", [(1, 64, 16), (3, 700, 50), (8, 4096, 512)])
def test_stacked_segment_sum(monkeypatch, r, e, n):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    rng = _rng(2)
    vals = jnp.asarray(rng.standard_normal((r, e)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(0, n, size=e), dtype=jnp.int32)
    got = np.asarray(ops.stacked_segment_sum(vals, ids, n))
    want = np.stack([
        np.bincount(np.asarray(ids), weights=np.asarray(vals)[i],
                    minlength=n)[:n]
        for i in range(r)
    ]).astype(np.float32)
    assert got.shape == (r, n)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    monkeypatch.setenv("REPRO_PALLAS", "off")
    got2 = np.asarray(ops.stacked_segment_sum(vals, ids, n))
    np.testing.assert_allclose(got2, want, rtol=1e-5, atol=1e-5)


def test_tree_stack_unstack_roundtrip():
    from repro.kernels import ops

    trees = [
        {"frontier": jnp.arange(6, dtype=jnp.float32) * i,
         "acc": (jnp.ones((2, 3)) * i, jnp.zeros((4,)) + i)}
        for i in range(5)
    ]
    stacked = ops.tree_stack(trees)
    assert stacked["frontier"].shape == (5, 6)
    assert stacked["acc"][0].shape == (5, 2, 3)
    back = ops.tree_unstack(stacked)
    assert len(back) == 5
    for orig, got in zip(trees, back):
        assert jax.tree.structure(orig) == jax.tree.structure(got)
        for a, b in zip(jax.tree.leaves(orig), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# custom VJPs: gradients through the kernels match the references'
# ---------------------------------------------------------------------------

@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")


def _grad_pair(kernel_fn, ref_fn, args, argnums):
    """(value, grads) of sum(out * cot) for the kernel path and the ref."""
    out = ref_fn(*args)
    cot = jnp.asarray(_rng(99).standard_normal(out.shape), out.dtype)
    loss = lambda fn: (lambda *a: (fn(*a) * cot).sum())
    got = jax.value_and_grad(loss(kernel_fn), argnums=argnums)(*args)
    want = jax.value_and_grad(loss(ref_fn), argnums=argnums)(*args)
    return got, want


def _assert_grads_close(got, want, tol):
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=tol, atol=tol)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol, atol=tol)


def test_edge_segment_sum_grad_matches_ref(interpret_mode):
    from repro.kernels import ops

    rng = _rng(21)
    e, n, d = 700, 90, 16
    values = jnp.asarray(rng.standard_normal((e, d)), jnp.float32)
    # ids outside [0, N) are dropped forward and must get no gradient
    dst = jnp.asarray(rng.integers(-3, n + 5, size=e), jnp.int32)
    got, want = _grad_pair(lambda v: ops.edge_segment_sum(v, dst, n),
                           lambda v: ref.edge_segment_sum(v, dst, n),
                           (values,), 0)
    _assert_grads_close(got, want, 1e-4)
    assert np.all(np.asarray(got[1])[np.asarray((dst < 0) | (dst >= n))] == 0)


def test_csr_segment_sum_grad_matches_ref(interpret_mode):
    from repro.kernels import ops

    rng = _rng(22)
    n, d = 70, 8
    deg = rng.integers(0, 12, size=n)
    indptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]), jnp.int32)
    values = jnp.asarray(rng.standard_normal((int(deg.sum()), d)), jnp.float32)
    got, want = _grad_pair(lambda v: ops.csr_segment_sum(v, indptr, n),
                           lambda v: ref.csr_segment_sum(v, indptr, n),
                           (values,), 0)
    _assert_grads_close(got, want, 1e-4)


def test_embedding_bag_grad_matches_ref(interpret_mode):
    from repro.kernels import ops

    rng = _rng(23)
    v, d, b, l = 300, 8, 40, 5
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    idx = rng.integers(0, v, size=(b, l))
    w = (rng.random((b, l)) < 0.8).astype(np.float32) * rng.random((b, l))
    idx[w == 0] = -1                      # padding slots: index -1, weight 0
    idx = jnp.asarray(idx, jnp.int32)
    w = jnp.asarray(w, jnp.float32)
    safe = jnp.clip(idx, 0, v - 1)        # the ref gathers; mask by weight 0
    got, want = _grad_pair(lambda t, w: ops.embedding_bag(t, idx, w),
                           lambda t, w: ref.embedding_bag(t, safe, w),
                           (table, w), (0, 1))
    # padding slots: the kernel gives no weight gradient, the ref's gather
    # of a clipped row does; compare live slots only
    live = np.asarray(w) != 0
    _assert_grads_close((got[0], got[1][0]), (want[0], want[1][0]), 1e-4)
    np.testing.assert_allclose(np.asarray(got[1][1])[live],
                               np.asarray(want[1][1])[live], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal,kv_len", [(True, None), (False, 200)])
def test_flash_attention_grad_matches_ref(interpret_mode, causal, kv_len):
    from repro.kernels import ops

    rng = _rng(24)
    b, h, s, dh = 1, 2, 256, 32
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, dh)) * 0.3, jnp.float32)
               for _ in range(3))
    mask = None if kv_len is None else jnp.asarray(kv_len, jnp.int32)
    got, want = _grad_pair(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=causal, block_q=64,
                                            block_kv=64, kv_len_mask=mask),
        lambda q, k, v: ref.attention(q, k, v, causal=causal, kv_len=kv_len),
        (q, k, v), (0, 1, 2))
    _assert_grads_close(got, want, 2e-4)


# ---------------------------------------------------------------------------
# no hidden fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("forced", ["interpret", "off"])
def test_forced_mode_is_an_error_on_tpu(monkeypatch, forced):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS", forced)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="REPRO_PALLAS"):
        ops.use_pallas()


def test_unknown_pallas_mode_is_an_error(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS", "sometimes")
    with pytest.raises(ValueError, match="REPRO_PALLAS"):
        ops.use_pallas()


def test_flash_attention_refuses_ragged_blocks(interpret_mode):
    from repro.kernels import ops

    q = jnp.zeros((1, 1, 96, 32), jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        ops.flash_attention(q, q, q, block_q=64, block_kv=64)
