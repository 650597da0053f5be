"""Fault-tolerant training loop (DESIGN.md §6).

Wires together: stateless data pipeline (exact resume), periodic + preemption
checkpointing (atomic, async), straggler detection, heartbeats, optional
gradient compression, and metrics logging.  The loop is family-agnostic: it
drives any Arch from the registry.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.data.pipeline import StatelessPipeline
from repro.distributed.fault import HeartbeatRegistry, PreemptionGuard, StragglerDetector
from repro.train.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.train.metrics import MetricsLogger


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    keep_checkpoints: int = 3
    log_path: Optional[str] = None
    log_every: int = 10
    async_checkpoint: bool = True
    straggler_threshold: float = 3.0


@dataclasses.dataclass
class TrainResult:
    final_state: dict
    steps_run: int
    resumed_from: Optional[int]
    losses: list
    straggler_steps: list
    preempted: bool


def run_training(
    init_state_fn: Callable[[], dict],
    step_fn: Callable,
    pipeline: StatelessPipeline,
    config: TrainLoopConfig,
    preemption: Optional[PreemptionGuard] = None,
    shardings=None,
    batch_shardings=None,
) -> TrainResult:
    """Run (or resume) training to ``total_steps``.

    ``shardings`` places the initial (or restored) state and
    ``batch_shardings`` every batch; without them arrays go to the default
    device."""
    logger = MetricsLogger(config.log_path, config.log_every)
    straggler = StragglerDetector(threshold=config.straggler_threshold)
    heartbeat = HeartbeatRegistry()
    preemption = preemption or PreemptionGuard(install=False)

    # ---- resume ------------------------------------------------------------
    resumed_from = None
    state = init_state_fn()
    if shardings is not None:
        state = jax.device_put(state, shardings)
    if config.checkpoint_dir:
        last = latest_step(config.checkpoint_dir)
        if last is not None:
            state = restore_checkpoint(config.checkpoint_dir, state,
                                       step=last, shardings=shardings)
            resumed_from = last
    start_step = int(np.asarray(state["step"]))

    ckpt = (AsyncCheckpointer(config.checkpoint_dir, keep=config.keep_checkpoints)
            if config.checkpoint_dir and config.async_checkpoint else None)

    step_jit = jax.jit(step_fn, donate_argnums=(0,))
    losses = []
    preempted = False
    steps_run = 0
    try:
        for step, batch in pipeline.iterate(start_step,
                                            config.total_steps - start_step):
            t0 = time.perf_counter()
            batch = jax.device_put(batch, batch_shardings)
            state, metrics = step_jit(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            steps_run += 1
            heartbeat.tick("trainer")
            dt = time.perf_counter() - t0
            straggler.record(step, dt)
            logger.log(step, {**metrics, "lr_step": step})

            at_boundary = config.checkpoint_dir and (
                (step + 1) % config.checkpoint_every == 0
                or step + 1 == config.total_steps
            )
            if preemption.should_stop():
                preempted = True
                at_boundary = bool(config.checkpoint_dir)
            if at_boundary:
                if ckpt is not None:
                    ckpt.save(step + 1, state)
                else:
                    from repro.train.checkpoint import save_checkpoint
                    save_checkpoint(config.checkpoint_dir, step + 1, state,
                                    keep=config.keep_checkpoints)
            if preempted:
                break
    finally:
        if ckpt is not None:
            ckpt.close()

    return TrainResult(
        final_state=state,
        steps_run=steps_run,
        resumed_from=resumed_from,
        losses=losses,
        straggler_steps=straggler.flagged_steps,
        preempted=preempted,
    )
