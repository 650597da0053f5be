"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) combination:
``jax.jit(step, in_shardings, out_shardings).lower(specs).compile()`` must
succeed on the production meshes (16x16 single-pod and 2x16x16 multi-pod,
512 placeholder CPU devices, modeling ``MODELED_KIND`` chips).  The
compiled artifact yields
``memory_analysis()`` (proves per-device fit) and ``cost_analysis()`` +
SPMD HLO (feeds the roofline, deliverable g).

Results are written incrementally to ``benchmarks/results/dryrun/*.json``
(idempotent: existing results are skipped unless --force), so the sweep can
be resumed after interruption.

The placeholder devices come from ``XLA_FLAGS``: ``main()`` folds
``--xla_force_host_platform_device_count=512`` into the caller's flags and
pins the CPU platform before any backend starts; importing this module
changes neither (library users set their own flags).

Usage:
    python -m repro.launch.dryrun                        # full sweep
    python -m repro.launch.dryrun --arch qwen2-1.5b      # one arch
    python -m repro.launch.dryrun --arch qwen2-1.5b --cell train_4k --mesh pod
"""

import argparse
import json
import os
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_arch
from repro.distributed.meshctx import use_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import derive_terms, peaks

# the chip the placeholder devices stand in for: its peaks (HBM capacity,
# FLOP/s, bandwidths) come from the roofline table
MODELED_KIND = "TPU v5 lite"

RESULTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), "benchmarks", "results", "dryrun"
)


def _result_path(arch_id: str, cell: str, mesh_name: str) -> str:
    safe = arch_id.replace("/", "_").replace(".", "_")
    return os.path.join(RESULTS_DIR, f"{safe}__{cell}__{mesh_name}.json")


def _mem_to_json(mem) -> dict:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes", "peak_memory_in_bytes"):
        try:
            out[attr] = int(getattr(mem, attr))
        except Exception:
            pass
    return out


def _per_device_bytes(mem_json: dict) -> int:
    """Per-device footprint: XLA's liveness-aware peak when available
    (arguments are donated/persistent, so add them), else args+temps."""
    args = mem_json.get("argument_size_in_bytes", 0)
    if "peak_memory_in_bytes" in mem_json:
        return mem_json["peak_memory_in_bytes"] + args
    return (args + mem_json.get("temp_size_in_bytes", 0)
            - mem_json.get("alias_size_in_bytes", 0))


def run_cell(arch_id: str, cell_name: str, mesh_name: str,
             force: bool = False, variant: str = "") -> dict:
    """Lower + compile one (arch, cell, mesh); returns the result record."""
    path = _result_path(arch_id, cell_name, mesh_name + variant)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    arch = get_arch(arch_id)
    cell = {c.name: c for c in arch.shapes()}[cell_name]
    record = {
        "arch": arch_id, "cell": cell_name, "mesh": mesh_name,
        "kind": cell.kind, "device_kind": MODELED_KIND, "status": "pending",
    }
    if cell.skip:
        record.update(status="skipped", reason=cell.skip)
        _write(path, record)
        return record

    multi_pod = mesh_name == "pod2"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.perf_counter()
    try:
        state_sh, batch_sh = arch.shardings(mesh, cell)
        try:
            state_specs = arch.state_specs(cell, reduced=False, mesh=mesh)
        except TypeError:
            state_specs = arch.state_specs(cell, reduced=False)
        batch_specs = arch.batch_specs(cell, reduced=False)
        try:
            step = arch.make_step(cell, reduced=False, mesh=mesh)
        except TypeError:
            step = arch.make_step(cell, reduced=False)

        # donate the state: decode steps alias caches in place, train steps
        # alias params/optimizer — matches production and halves peak memory.
        # out_shardings must mirror the input state shardings or XLA cannot
        # alias the donated buffers.
        if cell.kind == "train":
            out_sh = (state_sh, None)
        elif cell.kind == "decode":
            out_sh = (None, state_sh)
        else:
            out_sh = None
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=out_sh, donate_argnums=(0,))
        with use_mesh(mesh):
            lowered = jitted.lower(state_specs, batch_specs)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()

        # exact per-device cost accounting. cost_analysis counts loop bodies
        # once, so scanned layer stacks (LMs) are audited via two fully
        # unrolled variants (L=1, L=2) and extrapolated: exact for
        # layer-homogeneous stacks. GNN/recsys archs are python-unrolled
        # already; the dimenet ring gather gets an analytic correction.
        from repro.launch.roofline import collective_bytes_from_hlo
        from repro.perf_flags import enabled as _opt

        def _coll_bytes(hlo_text: str) -> float:
            # bf16-wire correction: the StableHLO ships bf16 all-gathers when
            # the bf16gather/gnnbf16 flags are on, but the CPU backend
            # legalizes sub-f32 collectives to f32 (verified; TPU ships bf16
            # natively) — halve the all-gather bytes to reflect the target.
            kinds = collective_bytes_from_hlo(hlo_text)
            if _opt("bf16gather") or _opt("gnnbf16"):
                kinds = dict(kinds)
                kinds["all-gather"] = kinds.get("all-gather", 0) * 0.5
            return float(sum(kinds.values()))
        if getattr(arch, "family", "") == "lm" and hasattr(arch, "cost_variant"):
            from repro.kernels import ops as kops
            samples = []
            for n_l in (1, 2):
                va = arch.cost_variant(n_l)
                v_state_sh, v_batch_sh = va.shardings(mesh, cell)
                with kops.attention_unroll(), use_mesh(mesh):
                    v_comp = jax.jit(
                        va.make_step(cell), in_shardings=(v_state_sh, v_batch_sh)
                    ).lower(va.state_specs(cell), va.batch_specs(cell)).compile()
                v_cost = v_comp.cost_analysis() or {}
                samples.append({
                    "flops": float(v_cost.get("flops", 0.0)),
                    "bytes": float(v_cost.get("bytes accessed", 0.0)),
                    "coll": _coll_bytes(v_comp.as_text()),
                })
            l_full = arch.config(False).n_layers
            def _extrap(key):
                return samples[0][key] + (l_full - 1) * (
                    samples[1][key] - samples[0][key])
            flops_dev = _extrap("flops")
            bytes_dev = _extrap("bytes")
            coll_dev = _extrap("coll")
            cost_audit = {"method": "unrolled L1/L2 extrapolation",
                          "samples": samples}
        else:
            flops_dev = float(cost.get("flops", 0.0))
            bytes_dev = float(cost.get("bytes accessed", 0.0))
            coll_dev = _coll_bytes(hlo)
            cost_audit = {"method": "direct (python-unrolled layers)"}
            if arch_id == "dimenet" and cell_name == "ogb_products":
                # ring-gather fori_loop bodies count once; add the analytic
                # per-device ring traffic: each gather streams the full table
                # past every device (E rows x width x 4B), x (2 geo + n_blocks
                # m_kj gathers) for fwd and again for the ring-reduce bwd.
                e = cell.dims["n_edges"]
                n_blocks = arch.config(False).n_blocks
                ring = 2.0 * (2 * e * 4 * 4 + n_blocks * e * 128 * 4)
                coll_dev += ring
                cost_audit["ring_correction_bytes"] = ring

        terms = derive_terms(
            arch_id, cell_name, mesh_name, chips, cost, hlo,
            model_flops=arch.model_flops(cell), device_kind=MODELED_KIND,
        )
        # overwrite with audited per-device numbers (x chips = global)
        terms.hlo_flops = flops_dev * chips
        terms.hlo_bytes = bytes_dev * chips
        terms.collective_bytes = coll_dev * chips
        terms.__post_init__()
        mem_json = _mem_to_json(mem)
        per_dev = _per_device_bytes(mem_json)
        record.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=mem_json,
            per_device_bytes=per_dev,
            fits_hbm=bool(per_dev < peaks(MODELED_KIND).hbm_bytes),
            cost={k: cost[k] for k in ("flops", "bytes accessed")
                  if k in cost},
            cost_audit=cost_audit,
            roofline=terms.to_json(),
            hlo_collective_ops=_collective_op_counts(hlo),
        )
    except Exception as e:  # record failures — they are bugs to fix
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    _write(path, record)
    return record


def _collective_op_counts(hlo: str) -> dict:
    import re
    counts: dict[str, int] = {}
    for kind in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                 "collective-permute"):
        counts[kind] = len(re.findall(rf"\b{kind}(?:-start)?\(", hlo))
    return counts


def _write(path: str, record: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1)
    os.replace(tmp, path)


def _placeholder_devices(n: int = 512) -> None:
    """Give the CPU backend ``n`` devices and pin it, keeping the caller's
    other XLA flags.  Must run before the first JAX backend call."""
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())
    jax.config.update("jax_platforms", "cpu")


def main() -> None:
    _placeholder_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--cell", default=None, help="one cell name (default: all)")
    ap.add_argument("--mesh", default=None, choices=[None, "pod", "pod2"],
                    help="pod=16x16, pod2=2x16x16 (default: both)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = [args.mesh] if args.mesh else ["pod", "pod2"]

    n_ok = n_skip = n_err = 0
    for arch_id in archs:
        arch = get_arch(arch_id)
        for cell in arch.shapes():
            if args.cell and cell.name != args.cell:
                continue
            for mesh_name in meshes:
                t0 = time.perf_counter()
                rec = run_cell(arch_id, cell.name, mesh_name, force=args.force)
                dt = time.perf_counter() - t0
                status = rec["status"]
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_err += status == "error"
                line = f"[{status:7s}] {arch_id:24s} {cell.name:16s} {mesh_name:5s} ({dt:6.1f}s)"
                if status == "ok":
                    r = rec["roofline"]
                    line += (f" dom={r['dominant']:10s}"
                             f" comp={r['compute_s']:.2e}s"
                             f" mem={r['memory_s']:.2e}s"
                             f" coll={r['collective_s']:.2e}s"
                             f" perdev={rec['per_device_bytes']/1e9:.2f}GB")
                elif status == "error":
                    line += " " + rec["error"][:120]
                print(line, flush=True)
    print(f"\nDRY-RUN SUMMARY: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
