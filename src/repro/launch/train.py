"""End-to-end training driver.

Picks an architecture from the registry, builds the (elastic) mesh, the
stateless data pipeline, and runs the fault-tolerant training loop with
checkpointing.  Full configs run on the chip; on a CPU use ``--reduced``.
With ``--use-mesh`` the state and every batch are placed with the arch's
sharding rules over all local devices.  The process exits non-zero when a
step fails (an exception, or a loss that is not finite).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
        --steps 200 --ckpt-dir /tmp/ck
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Callable, Optional

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_arch
from repro.data.pipeline import StatelessPipeline, lm_batch_maker, recsys_batch_maker
from repro.distributed.fault import PreemptionGuard
from repro.distributed.meshctx import use_mesh
from repro.launch.mesh import make_elastic_mesh
from repro.train.loop import TrainLoopConfig, TrainResult, run_training


def _make_pipeline(arch, cell, reduced: bool):
    cfg = arch.config(reduced)
    if arch.family == "lm":
        dims = arch._dims(cell, reduced)
        return StatelessPipeline(
            lm_batch_maker(cfg.vocab, dims["batch"], dims["seq"]))
    if arch.family == "recsys":
        b = arch._batch_size(cell, reduced)
        return StatelessPipeline(recsys_batch_maker(cfg, b))

    # GNN: synthetic graphs via the arch's own example_batch, re-seeded per step
    def make(seed, step, shard, n_shards):
        batch = arch.example_batch(cell, seed=seed * 10007 + step,
                                   reduced=reduced)
        batch.pop("n_graphs", None)
        return batch

    return StatelessPipeline(make)


@dataclasses.dataclass
class TrainRun:
    """What a training run produced, plus the pieces that built it: the
    un-jitted step, the batch maker and the shardings (None without a mesh)."""

    result: TrainResult
    step_fn: Callable
    batch_at: Callable[[int], dict]
    mesh: Optional[jax.sharding.Mesh]
    state_shardings: Optional[dict]
    batch_shardings: Optional[dict]

    @property
    def failed_steps(self) -> list[int]:
        return [i for i, loss in enumerate(self.result.losses)
                if not np.isfinite(loss)]


def train(arch_id: str, cell_name: Optional[str] = None, steps: int = 100,
          reduced: bool = False, mesh_devices: bool = False,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          log: Optional[str] = None) -> TrainRun:
    arch = get_arch(arch_id)
    cells = [c for c in arch.shapes() if c.kind == "train" and not c.skip]
    cell = next((c for c in cells if c.name == cell_name), cells[0])
    print(f"training {arch_id} on cell {cell.name} "
          f"(reduced={reduced}, devices={len(jax.devices())})")

    mesh = make_elastic_mesh() if mesh_devices else None
    try:
        step_fn = arch.make_step(cell, reduced=reduced, mesh=mesh)
    except TypeError:
        step_fn = arch.make_step(cell, reduced=reduced)

    def init():
        try:
            return arch.init_state(jax.random.PRNGKey(0), cell,
                                   reduced=reduced, mesh=mesh)
        except TypeError:
            return arch.init_state(jax.random.PRNGKey(0), cell,
                                   reduced=reduced)

    state_sh = batch_sh = None
    if mesh is not None:
        state_sh, batch_sh = arch.shardings(mesh, cell, reduced)
    pipeline = _make_pipeline(arch, cell, reduced)
    guard = PreemptionGuard(install=True)
    cfg = TrainLoopConfig(
        total_steps=steps,
        checkpoint_every=ckpt_every,
        checkpoint_dir=ckpt_dir,
        log_path=log,
    )
    try:
        with use_mesh(mesh):
            result = run_training(init, step_fn, pipeline, cfg, preemption=guard,
                                  shardings=state_sh, batch_shardings=batch_sh)
    finally:
        pipeline.close()
    return TrainRun(result=result, step_fn=step_fn, batch_at=pipeline.batch_at,
                    mesh=mesh, state_shardings=state_sh, batch_shardings=batch_sh)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log", default=None)
    ap.add_argument("--use-mesh", action="store_true",
                    help="build an elastic mesh over available devices")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run = train(args.arch, args.cell, steps=args.steps, reduced=args.reduced,
                mesh_devices=args.use_mesh, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, log=args.log)
    result = run.result
    print(f"steps run: {result.steps_run}  resumed_from: {result.resumed_from}")
    if result.losses:
        print(f"loss: {np.mean(result.losses[:5]):.4f} -> "
              f"{np.mean(result.losses[-5:]):.4f}")
    if result.straggler_steps:
        print(f"straggler steps: {result.straggler_steps}")
    if run.failed_steps:
        print(f"non-finite loss at steps {run.failed_steps}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
