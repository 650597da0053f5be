"""Chip smoke test: GraphLake's main path, end to end, on a TPU.

One process, every phase in it, no child processes.  Data is generated
fresh from seeds into ``<checkout>/.smoke_data`` (cleared first, removed at
exit).  Each phase prints what it found; the last line of standard output is
one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It is printed only when every phase passed.  Any failed phase, parity miss,
non-TPU backend or ``REPRO_PALLAS`` override exits non-zero with no result.

    python chip_smoke.py             # one chip:
        1. engine + serving: LDBC SF 1 lake -> startup() -> the installed BI
           suite through QueryServer (48 requests), every result ok and
           identical to a solo ExecOptions(pushdown=False) run;
        2. algorithms: Graph500 RMAT scale 22 lake -> PageRank (20 iters)
           and WCC on the chip vs float64 numpy power iteration and numpy
           union-find over the same dense edges;
        3. gin-tu at its published widths on the minibatch_lg cell through
           the launch/train path (5 steps), a compiled step that holds the
           Pallas EdgeScan kernel, and kernel + gradient parity with
           kernels/ref.py at D=602 and D=64.
    python chip_smoke.py --chips 4   # only the sharded two-pass EdgeScan:
        gin-tu minibatch_lg on the (1, 4) mesh, state and batches spread
        over all four chips, loss vs the single-device loss.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
WORK = ROOT / ".smoke_data"

LDBC_SF = 1.0
SERVE_REQUESTS = 48
GRAPH500_SCALE = 22
PAGERANK_ITERS = 20
GIN_STEPS = 5
MESH_STEPS = 3

# PageRank: the chip iterates in f32, the reference in f64.  Per-vertex
# ranks must agree to 1e-4 relative (plus 1e-3 of the uniform rank 1/n for
# ranks near zero); the L1 distance over all vertices to 1e-5 of the total.
PR_RTOL, PR_ATOL_OF_UNIFORM, PR_L1_RTOL = 1e-4, 1e-3, 1e-5
# kernel vs reference in f32: the one-hot matmul runs at HIGHEST precision,
# so only summation order differs
KERNEL_TOL = 1e-4
# sharded vs single-device GIN loss: the sharded pass-1 gather ships bf16
# rows (the gnnbf16 flag, on by default), the single-device path gathers f32
MESH_LOSS_RTOL = 5e-3


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info(expect_count: int) -> dict:
    """The device as JAX reports it; anything but ``expect_count`` TPUs
    with the Pallas kernels selected is a failure (no CPU fallback)."""
    import jax

    from repro.kernels import ops

    backend = jax.default_backend()
    require(backend == "tpu", f"backend is {backend!r}, not 'tpu'")
    require(ops.use_pallas(), "Pallas kernels are not selected on the TPU")
    devs = jax.devices()
    require(len(devs) == expect_count,
            f"{len(devs)} devices visible, expected {expect_count}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 1: engine + serving
# ---------------------------------------------------------------------------

def phase_serving(work: Path, sf: float = LDBC_SF,
                  requests: int = SERVE_REQUESTS) -> None:
    from repro.launch.serve import serve

    report = serve(str(work / "ldbc"), sf=sf, requests=requests, workers=2,
                   fresh=True, verify=True, log=lambda m: say(f"  {m}"))
    require(report.n_requests >= 40, f"only {report.n_requests} requests")
    require(not report.errors, f"{len(report.errors)} requests failed: "
            f"{report.errors[:3]}")
    require(not report.mismatched,
            f"{len(report.mismatched)} results differ from pushdown=False solo")
    say(f"serving: {report.n_ok}/{report.n_requests} ok, all identical to "
        f"solo pushdown=False; startup {report.startup_s:.3f}s "
        f"({report.startup_mode}); p50 {report.latency['p50_s']:.4f}s "
        f"p99 {report.latency['p99_s']:.4f}s")


# ---------------------------------------------------------------------------
# phase 2: graph algorithms on the device
# ---------------------------------------------------------------------------

def pagerank_reference(src, dst, n: int, damping: float, iters: int,
                       tol: float):
    """Plain float64 power iteration with the engine's semantics: dangling
    mass spread uniformly, early stop when the L1 change drops below tol."""
    import numpy as np

    out_deg = np.bincount(src, minlength=n).astype(np.float64)
    inv_deg = 1.0 / np.maximum(out_deg, 1.0)
    dangling = out_deg == 0
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        agg = np.bincount(dst, weights=(rank * inv_deg)[src], minlength=n)
        new = (1.0 - damping) / n + damping * (agg + rank[dangling].sum() / n)
        done = np.abs(new - rank).sum() < tol
        rank = new
        if done:
            break
    return rank


def wcc_reference(src, dst, n: int):
    """Union-find with min-root hooking and full path compression: every
    component ends as one tree rooted at its smallest vertex id."""
    import numpy as np

    parent = np.arange(n, dtype=np.int64)
    while True:
        while True:                               # compress to roots
            grand = parent[parent]
            if np.array_equal(grand, parent):
                break
            parent = grand
        ru, rv = parent[src], parent[dst]
        split = ru != rv
        if not split.any():
            return parent
        lo = np.minimum(ru[split], rv[split])
        hi = np.maximum(ru[split], rv[split])
        np.minimum.at(parent, hi, lo)             # hook larger root under smaller


def phase_algorithms(work: Path, scale: int = GRAPH500_SCALE,
                     iters: int = PAGERANK_ITERS) -> None:
    import numpy as np

    from repro.core import algorithms
    from repro.core.engine import GraphLakeEngine
    from repro.data.graph500 import generate_graph500, graph500_schema
    from repro.lakehouse.objectstore import ObjectStore, StoreConfig

    t0 = time.perf_counter()
    store = ObjectStore(StoreConfig(root=str(work / f"graph500_s{scale}")))
    schema = generate_graph500(store, scale=scale)
    t_gen = time.perf_counter() - t0
    # topology-only startup without writing the built topology back to the
    # lake: at scale 22 that copy would be ~4 GB of disk the phase never reads
    engine = GraphLakeEngine(store, schema, materialize_topology=False)
    try:
        t0 = time.perf_counter()
        engine.startup()
        t_start = time.perf_counter() - t0
        src, dst = engine.concat_edges("Edge")
        n = engine.topology.n_vertices("Node")
        say(f"  graph500 scale {scale}: {n} vertices, {len(src)} edges; "
            f"lake written in {t_gen:.1f}s, startup {t_start:.1f}s")

        t0 = time.perf_counter()
        pr = algorithms.pagerank(engine, "Edge", max_iters=iters)
        t_pr = time.perf_counter() - t0
        t0 = time.perf_counter()
        labels = algorithms.wcc(engine, "Edge")
        t_wcc = time.perf_counter() - t0
    finally:
        engine.close()

    t0 = time.perf_counter()
    ref = pagerank_reference(src, dst, n, 0.85, iters, tol=1e-7)
    err = np.abs(pr.astype(np.float64) - ref)
    l1_rel = float(err.sum() / ref.sum())
    worst = float((err / (PR_RTOL * ref + PR_ATOL_OF_UNIFORM / n)).max())
    require(worst <= 1.0 and l1_rel <= PR_L1_RTOL,
            f"PageRank off the f64 reference: max |err| {err.max():.3e}, "
            f"worst tolerance share {worst:.3f}, L1 rel {l1_rel:.3e}")
    ref_labels = wcc_reference(src, dst, n)
    t_ref = time.perf_counter() - t0
    mismatch = int((labels != ref_labels).sum())
    require(mismatch == 0, f"WCC labels differ from union-find at {mismatch} "
            f"vertices")
    say(f"algorithms: PageRank {iters} iters {t_pr:.1f}s, max |err| "
        f"{err.max():.3e} (worst {worst:.3f} of tolerance), L1 rel "
        f"{l1_rel:.3e}; WCC {t_wcc:.1f}s, {len(np.unique(labels))} components, "
        f"labels identical to union-find; references {t_ref:.1f}s")


# ---------------------------------------------------------------------------
# phase 3: one registry model at full width
# ---------------------------------------------------------------------------

def _kernel_parity(batch: dict, n: int, widths=(602, 64)) -> None:
    """kops.edge_segment_sum (the Pallas kernel on the chip) and its
    gradient against kernels/ref.py on the batch's own edge ids (which
    include ids past N, dropped by both)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.kernels import ref

    dst = jnp.asarray(batch["dst"])
    rng = np.random.default_rng(5)

    def fwd_bwd(seg_sum):
        # arrays travel as arguments: closed-over ones would be baked into
        # the executable as constants (hundreds of MB at these widths)
        def run(v, ids, cot):
            loss = lambda v: (seg_sum(v, ids, n) * cot).sum()
            return seg_sum(v, ids, n), jax.grad(loss)(v)
        return jax.jit(run)

    kern, oracle = fwd_bwd(kops.edge_segment_sum), fwd_bwd(ref.edge_segment_sum)
    for d in widths:
        values = jnp.asarray(rng.standard_normal((dst.shape[0], d)), jnp.float32)
        cot = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        hlo = kern.lower(values, dst, cot).compile().as_text()
        require("tpu_custom_call" in hlo, f"D={d}: no Pallas kernel in the program")
        got_out, got_grad = kern(values, dst, cot)
        want_out, want_grad = oracle(values, dst, cot)
        out_err = float(jnp.abs(got_out - want_out).max())
        grad_err = float(jnp.abs(got_grad - want_grad).max())
        require(out_err <= KERNEL_TOL and grad_err <= KERNEL_TOL,
                f"D={d}: kernel vs ref max |err| out {out_err:.3e} grad "
                f"{grad_err:.3e} (tolerance {KERNEL_TOL})")
        say(f"  edge_segment_sum E={dst.shape[0]} N={n} D={d}: max |err| out "
            f"{out_err:.3e}, grad {grad_err:.3e} (tolerance {KERNEL_TOL})")


def phase_gin(reduced: bool = False, steps: int = GIN_STEPS) -> None:
    import jax
    import numpy as np

    from repro.launch.train import train

    t0 = time.perf_counter()
    run = train("gin-tu", "minibatch_lg", steps=steps, reduced=reduced)
    t_train = time.perf_counter() - t0
    losses = run.result.losses
    require(run.result.steps_run == steps, f"{run.result.steps_run}/{steps} steps ran")
    require(not run.failed_steps, f"non-finite loss at steps {run.failed_steps}")
    batch = run.batch_at(0)
    n = batch["x"].shape[0]

    t0 = time.perf_counter()
    hlo = jax.jit(run.step_fn, donate_argnums=(0,)).lower(
        run.result.final_state, batch).compile().as_text()
    kernels = hlo.count("tpu_custom_call")
    require(kernels > 0, "the compiled train step holds no Pallas kernel")
    say(f"  gin-tu minibatch_lg: N={n} E={batch['dst'].shape[0]} "
        f"d_in={batch['x'].shape[1]}; compiled step holds {kernels} "
        f"tpu_custom_call ops (checked in {time.perf_counter() - t0:.1f}s)")
    _kernel_parity(batch, n)
    say(f"gin: {steps} steps in {t_train:.1f}s incl. compile, losses "
        f"{[float(np.round(x, 6)) for x in losses]}, all finite")


# ---------------------------------------------------------------------------
# four chips: the sharded two-pass EdgeScan
# ---------------------------------------------------------------------------

def _spread(tree, n_dev: int, what: str) -> None:
    import jax

    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        devs = {s.device for s in leaf.addressable_shards}
        require(len(devs) == n_dev,
                f"{what}{jax.tree_util.keystr(path)} lives on {len(devs)} "
                f"of {n_dev} devices")


def phase_mesh(reduced: bool = False, steps: int = MESH_STEPS) -> None:
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.distributed.meshctx import use_mesh
    from repro.launch.train import train
    from repro.models.gnn import GIN
    from repro.models.gnn.common import local_dist, sharded_dist

    n_dev = len(jax.devices())
    run = train("gin-tu", "minibatch_lg", steps=steps, reduced=reduced,
                mesh_devices=True)
    mesh = run.mesh
    require(dict(mesh.shape) == {"data": 1, "model": n_dev},
            f"mesh is {dict(mesh.shape)}, expected (1, {n_dev})")
    require(run.result.steps_run == steps and not run.failed_steps,
            f"sharded training: {run.result.steps_run}/{steps} steps, "
            f"non-finite at {run.failed_steps}")
    _spread(run.result.final_state, n_dev, "state")
    batch = jax.device_put(run.batch_at(0), run.batch_shardings)
    _spread(batch, n_dev, "batch")
    rows = {s.data.shape[0] for s in batch["dst"].addressable_shards}
    require(rows == {batch["dst"].shape[0] // n_dev},
            f"edge ids are not split {n_dev} ways: shard rows {rows}")

    arch = get_arch("gin-tu")
    cell = next(c for c in arch.shapes() if c.name == "minibatch_lg")
    params = arch.init_state(jax.random.PRNGKey(0), cell, reduced=reduced)["params"]
    cfg = arch.config(reduced)
    with use_mesh(mesh):
        l_mesh = float(jax.jit(GIN(cfg, sharded_dist(mesh)).loss)(
            jax.device_put(params, run.state_shardings["params"]), batch))
    one = jax.devices()[0]
    l_one = float(jax.jit(GIN(cfg, local_dist()).loss)(
        jax.device_put(params, one), jax.device_put(run.batch_at(0), one)))
    rel = abs(l_mesh - l_one) / abs(l_one)
    require(rel <= MESH_LOSS_RTOL,
            f"sharded loss {l_mesh:.6f} vs single-device {l_one:.6f}: rel "
            f"{rel:.3e} > {MESH_LOSS_RTOL}")
    require(abs(run.result.losses[0] - l_mesh) <= 1e-5 * abs(l_mesh),
            f"trainer's first loss {run.result.losses[0]:.6f} is not the "
            f"sharded loss {l_mesh:.6f}")
    say(f"mesh: gin-tu minibatch_lg on {dict(mesh.shape)}, state + batch on "
        f"all {n_dev} devices, edges split {n_dev} ways; {steps} steps "
        f"losses {[float(np.round(x, 6)) for x in run.result.losses]}; loss "
        f"sharded {l_mesh:.6f} vs one device {l_one:.6f} (rel {rel:.3e}, "
        f"tolerance {MESH_LOSS_RTOL})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded GIN EdgeScan phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    try:
        from repro.launch.compile_cache import enable_compile_cache

        device = device_info(args.chips)
        say(f"device: {device}; compile cache {enable_compile_cache()}")
        shutil.rmtree(WORK, ignore_errors=True)
        WORK.mkdir(parents=True)
        phases = ([("mesh", phase_mesh)] if args.chips == 4 else
                  [("serving", lambda: phase_serving(WORK)),
                   ("algorithms", lambda: phase_algorithms(WORK)),
                   ("gin", phase_gin)])
        for name, phase in phases:
            t0 = time.perf_counter()
            say(f"== {name}")
            phase()
            say(f"== {name} passed in {time.perf_counter() - t0:.1f}s")
    except Exception as e:  # noqa: BLE001 - every failure ends the run typed
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        if not isinstance(e, SmokeFailure):
            import traceback
            traceback.print_exc()
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
