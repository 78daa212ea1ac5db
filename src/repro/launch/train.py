"""Training launcher: the sharded train loop on the devices present.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --shape train_4k [--local] [--steps N]

The mesh is (data, model) over every local device: ``mesh_shape`` makes
it as square as the device count allows (1x1 on one device, 2x2 on a
four-chip host).  ``--local`` runs the reduced config at a CPU-sized
shape on a 1x1 mesh (the same jit path, CPU-testable); the 512-device
production layout is compiled, not run, by ``repro.launch.dryrun``.

The loop: state initialised straight into its shardings -> jit(train_step)
with donation -> data pipeline (host batches placed on the data axis) ->
checkpoint manager (atomic, elastic restore) -> straggler monitor.
"""
from __future__ import annotations

import argparse
import math
from typing import Callable

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCH_IDS,
    ModelConfig,
    TrainConfig,
    get_config,
    reduced,
    shapes_for,
)
from repro.configs.base import ShapeConfig
from repro.ckpt import CheckpointManager, StragglerMonitor
from repro.data import SyntheticLM, make_data_config
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamWState, init_state
from repro.sharding import param_spec_tree, to_shardings
from repro.sharding.constraints import activation_sharding
from repro.train.step import TrainState, make_train_step

AXES = ("data", "model")


def mesh_shape(n_devices: int) -> tuple[int, int]:
    """(data, model) extents over ``n_devices``: the model axis is the
    largest power of two whose square divides the count."""
    model = 1 << (int(math.log2(n_devices)) // 2)
    while n_devices % model:
        model //= 2
    return n_devices // model, model


class Trainer:
    """One model's sharded training state and jitted step on a mesh.

    ``init_state()`` builds the parameters and optimizer moments
    directly in their shardings (never whole on one device);
    ``step(state, step)`` runs one optimizer step on batch ``step`` of
    the synthetic corpus and returns ``(state, metrics)``."""

    def __init__(self, cfg: ModelConfig, shape: ShapeConfig,
                 tcfg: TrainConfig, devices=None):
        devices = jax.devices() if devices is None else devices
        self.cfg, self.shape, self.tcfg = cfg, shape, tcfg
        self.mesh_shape = mesh_shape(len(devices))
        self.mesh = make_mesh(self.mesh_shape, AXES, devices=devices)
        self.model = build_model(cfg)
        self.rng = jax.random.PRNGKey(tcfg.seed)
        params_shape = jax.eval_shape(self.model.init, self.rng)
        pspec = to_shardings(self.mesh, param_spec_tree(
            cfg, params_shape, AXES, self.mesh_shape))
        self.state_sharding = TrainState(
            pspec, AdamWState(NamedSharding(self.mesh, P()), pspec, pspec))
        self.batch_sharding = NamedSharding(self.mesh, P("data", None))
        self.data = SyntheticLM(make_data_config(cfg, shape, tcfg.seed))
        self._step = jax.jit(make_train_step(self.model, tcfg),
                             donate_argnums=(0,))

    def sharding_scope(self):
        """The activation-sharding context every step runs under."""
        return activation_sharding(self.mesh, AXES, self.mesh_shape)

    def init_state(self) -> TrainState:
        def init_all():
            params = self.model.init(self.rng)
            return TrainState(params, init_state(params))
        with self.sharding_scope():
            return jax.jit(init_all, out_shardings=self.state_sharding)()

    def batch(self, step: int) -> dict:
        batch = self.data.batch(step)
        if self.cfg.frontend != "none":
            from repro.models.frontends import synth_frontend_embeddings
            batch["frontend"] = synth_frontend_embeddings(
                jax.random.fold_in(self.rng, step), self.cfg,
                batch["tokens"].shape[0])
        return jax.device_put(batch, self.batch_sharding)

    def step(self, state: TrainState, step: int) -> tuple[TrainState, dict]:
        with self.sharding_scope():
            return self._step(state, self.batch(step))


def run(trainer: Trainer, state: TrainState, start: int, stop: int, *,
        mgr: CheckpointManager | None = None, log_every: int = 10,
        on_metrics: Callable[[int, dict], None] | None = None
        ) -> TrainState:
    """Steps ``start`` .. ``stop - 1``: log, watch for stragglers, and
    checkpoint through ``mgr`` when one is given."""
    mon = StragglerMonitor(deadline_s=trainer.tcfg.step_deadline_s)
    for step in range(start, stop):
        mon.start()
        state, metrics = trainer.step(state, step)
        slow = mon.stop(step)
        if on_metrics is not None:
            on_metrics(step, metrics)
        if step % log_every == 0:
            print(f"step {step}: loss={float(metrics['loss']):.4f}"
                  f"{' [straggler]' if slow else ''}")
        if mgr is not None:
            mgr.maybe_save(step, state, force=mon.missed_deadline(step))
    return state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(ARCH_IDS))
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--local", action="store_true",
                    help="1-device mesh with a reduced config (CPU smoke)")
    ap.add_argument("--ckpt-dir", default="checkpoints/launch_train")
    ap.add_argument("--offload", action="store_true",
                    help="compile-time near-bank offload of the train step")
    ap.add_argument("--offload-mode", default="greedy",
                    choices=["greedy", "cost", "all_near", "all_far"],
                    help="offload decision backend (OffloadPolicy.mode): "
                         "'cost' prices each candidate segment near-vs-"
                         "far and declines unprofitable fusions")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="persistent offload-plan cache directory (sets "
                         "MPU_PLAN_CACHE): restarts and fleet peers "
                         "sharing DIR reuse serialized plans instead of "
                         "re-planning — corrupt entries are counted, "
                         "quarantined, and re-planned")
    args = ap.parse_args()
    if args.plan_cache:
        # env rather than plumbing: every mpu_offload wrapper built
        # below (train step, optimizer) picks it up at creation
        import os
        os.environ["MPU_PLAN_CACHE"] = args.plan_cache
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.local:
        cfg = reduced(cfg)
        shape = ShapeConfig("local", 128, 4, "train")
        devices = jax.devices()[:1]
    else:
        shape = next(s for s in shapes_for(cfg) if s.name == args.shape)
        devices = jax.devices()

    from repro.core.policy import OffloadPolicy

    tcfg = TrainConfig(total_steps=args.steps, checkpoint_every=50,
                       checkpoint_dir=args.ckpt_dir, offload=args.offload,
                       offload_policy=OffloadPolicy(mode=args.offload_mode)
                       if args.offload else None)
    trainer = Trainer(cfg, shape, tcfg, devices)
    check_offload_mesh(trainer)
    mgr = CheckpointManager(tcfg)
    state, start = mgr.restore_or_init(trainer.init_state)
    state = jax.device_put(state, trainer.state_sharding)
    run(trainer, state, start, tcfg.total_steps, mgr=mgr)
    print("done")


def check_offload_mesh(trainer: Trainer) -> None:
    """The offload rewriter's Pallas kernels are not partitioned across
    devices: refuse ``offload`` on a mesh of more than one device rather
    than run a different plan."""
    if trainer.tcfg.offload and trainer.mesh.size > 1:
        raise SystemExit(
            f"--offload runs its kernels on one device; this mesh has "
            f"{trainer.mesh.size} ({trainer.mesh_shape[0]}x"
            f"{trainer.mesh_shape[1]} data x model)")


if __name__ == "__main__":
    main()
