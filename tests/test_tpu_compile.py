"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The CPU suite runs every kernel in interpret mode, which accepts block
shapes and operations the chip's compiler refuses.  These tests compile each
kernel of the main path for one chip of a ``v5e:2x2`` topology that is
described, not attached, at the widths the main path uses, and require the
kernel (``tpu_custom_call``) in the compiled program.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  All kernel compiles stay in this one file for the same reason.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import GNN_SHAPE_DIMS, RECSYS_SHAPE_DIMS
from repro.configs.qwen2_1p5b import FULL as QWEN2
from repro.configs.xdeepfm import FULL as XDEEPFM
from repro.kernels.csr_expand import csr_segment_sum_pallas
from repro.kernels.edge_scan import edge_segment_sum_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas

GIN_LG = GNN_SHAPE_DIMS["minibatch_lg"]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs outside the checkout
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("d", [GIN_LG["d_feat"], 64])
def test_edge_segment_sum_compiles_at_gin_minibatch_lg(one_chip, d):
    """GIN's aggregate on minibatch_lg: D=602 (raw features) and D=64
    (hidden width, what projection pushdown aggregates)."""
    e, n = GIN_LG["n_edges"], GIN_LG["n_nodes"]
    text = _compiled_text(lambda v, i: edge_segment_sum_pallas(v, i, n), one_chip,
                          ((e, d), jnp.float32), ((e,), jnp.int32))
    assert "tpu_custom_call" in text


def test_csr_segment_sum_compiles_at_pagerank_widths(one_chip):
    """Graph500 scale 19 (524K vertices, 8.4M edges) with 8 rank channels:
    1,024 output blocks x 8,192 edge blocks of SMEM range statistics.  An
    (E, 8) f32 array is stored 128 lanes wide in HBM, so at scale 22 the
    same input (67M x 8) would take 32 GB and the compiler refuses it."""
    n, e, d = 1 << 19, 16 << 19, 8
    text = _compiled_text(lambda v, p: csr_segment_sum_pallas(v, p, n), one_chip,
                          ((e, d), jnp.float32), ((n + 1,), jnp.int32))
    assert "tpu_custom_call" in text


def test_embedding_bag_compiles_at_xdeepfm_widths(one_chip):
    """xdeepfm serve_p99: 512 requests x 4 multi-hot fields, bag 8, over the
    full 18.2M-row, 10-wide table."""
    v, d = XDEEPFM.total_vocab, XDEEPFM.embed_dim
    b = RECSYS_SHAPE_DIMS["serve_p99"]["batch"] * XDEEPFM.n_multihot
    bag = XDEEPFM.bag_size
    text = _compiled_text(embedding_bag_pallas, one_chip, ((v, d), jnp.float32),
                          ((b, bag), jnp.int32), ((b, bag), jnp.float32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_qwen2_head_width(one_chip):
    """qwen2-1.5b: 12 heads of width 128, bf16, a 4,096-token sequence."""
    h, dh = QWEN2.n_heads, QWEN2.d_model // QWEN2.n_heads
    qkv = ((1, h, 4096, dh), jnp.bfloat16)
    text = _compiled_text(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
                          one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text
