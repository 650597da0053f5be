"""Multi-device SPMD tests, run in subprocesses with forced host devices
(device count locks at first jax init, so each scenario gets its own
process).  Covers: sharded-vs-local GNN parity (two-pass EdgeScan pattern),
ring gather grads, sharded embedding lookup parity, and a minimal dry-run
lower+compile on a small mesh."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 600) -> str:
    prog = (
        f"import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(code)
    )
    # children force host devices: they never touch an accelerator, which
    # belongs to one process at a time
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_gnn_sharded_matches_local():
    """The shard_map two-pass EdgeScan (gather + segment + psum_scatter) must
    be numerically identical to the single-device path — loss AND grads."""
    _run("""
    import os as _os
    _os.environ["REPRO_OPTS"] = ""          # exact parity: f32 wire
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.models.gnn.common import local_dist, sharded_dist
    from repro.models.gnn import GIN, GINConfig

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    N, E = 64, 256          # divisible by 8 devices
    cfg = GINConfig(d_in=16, n_classes=4, task="node", n_layers=3, d_hidden=16)
    batch = dict(
        x=jnp.asarray(rng.standard_normal((N, 16)), jnp.float32),
        src=jnp.asarray(rng.integers(0, N, E), jnp.int32),
        dst=jnp.asarray(rng.integers(0, N, E), jnp.int32),
        edge_mask=jnp.ones(E, bool), node_mask=jnp.ones(N, bool),
        graph_ids=jnp.zeros(N, jnp.int32), n_graphs=8,
        graph_mask=jnp.ones(8, bool),
        labels=jnp.asarray(rng.integers(0, 4, N), jnp.int32),
        label_mask=jnp.ones(N, bool),
    )
    local = GIN(cfg, local_dist())
    params = local.init(jax.random.PRNGKey(0))
    l_loc = jax.jit(local.loss)(params, batch)
    g_loc = jax.jit(jax.grad(local.loss))(params, batch)

    shard = GIN(cfg, sharded_dist(mesh))
    l_sh = jax.jit(shard.loss)(params, batch)
    g_sh = jax.jit(jax.grad(shard.loss))(params, batch)

    np.testing.assert_allclose(float(l_loc), float(l_sh), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_loc), jax.tree.leaves(g_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    print("GNN sharded == local OK")
    """)


@pytest.mark.slow
def test_ring_gather_matches_allgather():
    _run("""
    import os as _os
    _os.environ["REPRO_OPTS"] = ""          # exact parity: f32 wire
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.gnn.common import sharded_dist
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    dist = sharded_dist(mesh)
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((128, 8)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 128, 64), jnp.int32)
    ring = jax.jit(lambda t: dist.gather_rows(t, idx, "ring"))(table)
    ag = jax.jit(lambda t: dist.gather_rows(t, idx, "allgather"))(table)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ag), rtol=1e-6)
    cot = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    g_r = jax.jit(jax.grad(lambda t: (dist.gather_rows(t, idx, "ring") * cot).sum()))(table)
    g_a = jax.grad(lambda t: (t[idx] * cot).sum())(table)
    np.testing.assert_allclose(np.asarray(g_r), np.asarray(g_a), rtol=1e-5)
    print("ring gather OK")
    """)


@pytest.mark.slow
def test_sharded_embedding_lookup_parity():
    """xDeepFM's shard_map table lookup (local masked take + psum) must match
    the single-device gather."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.recsys import XDeepFM, XDeepFMConfig
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = XDeepFMConfig(vocab_sizes=tuple([64] * 6 + [32] * 2), n_multihot=2,
                        bag_size=4, cin_layers=(8, 8), mlp_dims=(16,),
                        embed_dim=8)
    rng = np.random.default_rng(0)
    B = 16
    f_single = cfg.n_fields - cfg.n_multihot
    offs = cfg.field_offsets
    batch = {
        "idx_single": jnp.asarray(np.stack(
            [rng.integers(0, cfg.vocab_sizes[f], B) + offs[f]
             for f in range(f_single)], 1), jnp.int32),
        "idx_multi": jnp.asarray(np.stack(
            [rng.integers(0, cfg.vocab_sizes[f_single + f], (B, 4))
             + offs[f_single + f] for f in range(cfg.n_multihot)], 1), jnp.int32),
        "w_multi": jnp.ones((B, cfg.n_multihot, 4), jnp.float32),
        "labels": jnp.asarray(rng.integers(0, 2, B), jnp.int32),
    }
    local = XDeepFM(cfg, mesh=None)
    params = local.init(jax.random.PRNGKey(0))
    sharded = XDeepFM(cfg, mesh=mesh)
    l_loc = jax.jit(local.loss)(params, batch)
    l_sh = jax.jit(sharded.loss)(params, batch)
    np.testing.assert_allclose(float(l_loc), float(l_sh), rtol=1e-5)
    g_loc = jax.jit(jax.grad(local.loss))(params, batch)
    g_sh = jax.jit(jax.grad(sharded.loss))(params, batch)
    for a, b in zip(jax.tree.leaves(g_loc), jax.tree.leaves(g_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    print("sharded embedding OK")
    """)


@pytest.mark.slow
def test_lm_sharded_step_matches_single_device():
    """A reduced LM train step under a (2, 4) mesh with the production
    sharding rules must match the single-device result."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.distributed import sharding as shd
    from repro.distributed.meshctx import use_mesh
    arch = get_arch("qwen2-1.5b")
    cell = arch.shapes()[0]
    state = arch.init_state(jax.random.PRNGKey(0), cell, reduced=True)
    batch = arch.example_batch(cell, reduced=True)
    step = arch.make_step(cell, reduced=True)

    _, m1 = jax.jit(step)(jax.tree.map(jnp.copy, state), batch)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    state_sh = shd.lm_state_shardings(mesh, state)
    batch_sh = shd.lm_batch_shardings(mesh, batch)
    with use_mesh(mesh):
        _, m2 = jax.jit(step, in_shardings=(state_sh, batch_sh))(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-3)
    print("LM sharded step OK", float(m1["loss"]), float(m2["loss"]))
    """)


@pytest.mark.slow
def test_dryrun_compiles_reduced_cell():
    """dryrun machinery end-to-end on the real 512-device mesh for the
    cheapest cell (validates the deliverable-e path inside CI)."""
    _run("""
    import repro.launch.dryrun as dr
    rec = dr.run_cell("xdeepfm", "serve_p99", "pod", force=True)
    assert rec["status"] == "ok", rec
    assert rec["fits_hbm"], rec["per_device_bytes"]
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
    print("dryrun cell OK:", rec["roofline"]["dominant"])
    """, devices=512, timeout=900)


@pytest.mark.slow
def test_moe_ep_shardmap_parity():
    """The explicit expert-parallel dispatch (perf flag moe_ep) must match
    the pjit scatter path exactly — loss and grads (dropless sizes)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.moe import MoEConfig, moe_apply, moe_init
    from repro.distributed.meshctx import use_mesh

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = MoEConfig(d_model=32, d_ff_expert=16, n_experts=8, top_k=2, n_shared=1)
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32) * 0.5

    def loss(p, x):
        out, aux = moe_apply(p, cfg, x)
        return (out.astype(jnp.float32) ** 2).sum() + aux

    import os as _os
    _os.environ["REPRO_OPTS"] = ""
    l_ref = jax.jit(loss)(params, x)
    g_ref = jax.jit(jax.grad(loss))(params, x)
    _os.environ["REPRO_OPTS"] = "moe_ep"
    with use_mesh(mesh):
        l_ep = jax.jit(loss)(params, x)
        g_ep = jax.jit(jax.grad(loss))(params, x)
    np.testing.assert_allclose(float(l_ref), float(l_ep), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_ep)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)
    print("moe_ep parity OK")
    """)


def test_perf_flags_env_parsing(monkeypatch):
    from repro.perf_flags import enabled
    monkeypatch.delenv("REPRO_OPTS", raising=False)
    assert enabled("tri") and enabled("moe_ep")
    monkeypatch.setenv("REPRO_OPTS", "")
    assert not enabled("tri")
    monkeypatch.setenv("REPRO_OPTS", "tri, pushdown")
    assert enabled("tri") and enabled("pushdown") and not enabled("chunkloss")


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_meshes():
    """A checkpoint written under one mesh must restore onto a different mesh
    (elastic scaling): leaves are logical arrays, shardings re-applied."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.configs import get_arch
    from repro.distributed import sharding as shd
    from repro.distributed.meshctx import use_mesh
    from repro.train.checkpoint import save_checkpoint, restore_checkpoint

    arch = get_arch("qwen2-1.5b")
    cell = arch.shapes()[0]
    state = arch.init_state(jax.random.PRNGKey(0), cell, reduced=True)
    batch = arch.example_batch(cell, reduced=True)
    step = arch.make_step(cell, reduced=True)

    mesh_a = jax.make_mesh((2, 4), ("data", "model"),
                           axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh_a = shd.lm_state_shardings(mesh_a, state)
    with use_mesh(mesh_a):
        state_a, _ = jax.jit(step, in_shardings=(sh_a, None))(state, batch)
    root = tempfile.mkdtemp()
    save_checkpoint(root, 1, state_a)

    # restore onto a DIFFERENT mesh shape (8 x 1): elastic scale-out of DP
    mesh_b = jax.make_mesh((8, 1), ("data", "model"),
                           axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh_b = shd.lm_state_shardings(mesh_b, state)
    restored = restore_checkpoint(root, state_a, shardings=sh_b)
    with use_mesh(mesh_b):
        state_b, metrics = jax.jit(step, in_shardings=(sh_b, None))(restored, batch)
    assert np.isfinite(float(metrics["loss"]))
    # values identical regardless of mesh
    for a, b in zip(jax.tree.leaves(state_a["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("elastic restore OK, loss", float(metrics["loss"]))
    """)


@pytest.mark.slow
def test_gnn_bf16_wire_within_tolerance():
    """With the gnnbf16 flag the sharded path ships bf16 feature gathers;
    results must stay within bf16 tolerance of the f32 local path."""
    _run("""
    import os as _os
    _os.environ["REPRO_OPTS"] = "gnnbf16"
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.gnn.common import local_dist, sharded_dist
    from repro.models.gnn import GIN, GINConfig
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    N, E = 64, 256
    cfg = GINConfig(d_in=16, n_classes=4, task="node", n_layers=2, d_hidden=16)
    batch = dict(
        x=jnp.asarray(rng.standard_normal((N, 16)), jnp.float32),
        src=jnp.asarray(rng.integers(0, N, E), jnp.int32),
        dst=jnp.asarray(rng.integers(0, N, E), jnp.int32),
        edge_mask=jnp.ones(E, bool), node_mask=jnp.ones(N, bool),
        graph_ids=jnp.zeros(N, jnp.int32), n_graphs=8,
        graph_mask=jnp.ones(8, bool),
        labels=jnp.asarray(rng.integers(0, 4, N), jnp.int32),
        label_mask=jnp.ones(N, bool),
    )
    local = GIN(cfg, local_dist())
    params = local.init(jax.random.PRNGKey(0))
    l_loc = float(jax.jit(local.loss)(params, batch))
    shard = GIN(cfg, sharded_dist(mesh))
    l_sh = float(jax.jit(shard.loss)(params, batch))
    np.testing.assert_allclose(l_loc, l_sh, rtol=2e-2)
    print("gnnbf16 tolerance OK", l_loc, l_sh)
    """)
