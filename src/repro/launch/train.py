"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 100 --batch 8 --seq 512 [--mesh-data 1 --mesh-model 1] \
        [--topology allreduce|local_sgd] [--checkpoint-dir ckpt/]

Runs the production code path (pjit train step, Horn parallel dropout,
deterministic pipeline, async checkpoints, preemption handling) on a
``--mesh-data x --mesh-model`` mesh, which must not ask for more devices
than exist; reduced configs unless ``--full-config``.  ``--arch
horn-mnist`` runs the paper's MNIST experiment through the neuron-centric
engine instead.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs.base import (HornConfig, RunConfig, ShapeConfig,
                                TopologyConfig, get_model_config, list_archs,
                                reduced)
from repro.checkpoint.checkpointer import Checkpointer
from repro.core import steps as S
from repro.data.pipeline import SyntheticTokenPipeline, TokenPipelineConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.runtime.fault_tolerance import (NanGuard, PreemptionHandler,
                                           fault_tolerant_loop)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgdm"])
    ap.add_argument("--full-config", action="store_true",
                    help="use the full arch config (default: reduced)")
    ap.add_argument("--no-horn", action="store_true",
                    help="disable parallel dropout")
    ap.add_argument("--horn-groups", type=int, default=0)
    ap.add_argument("--topology", default="allreduce",
                    choices=["allreduce", "zero1", "local_sgd"])
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "horn-mnist":
        from repro.core.collective_trainer import train_mnist
        res = train_mnist(num_groups=args.horn_groups or 20,
                          batch_per_group=max(1, args.batch // 20),
                          num_steps=args.steps, lr=args.lr or 0.005,
                          eval_every=max(50, args.steps // 5),
                          seed=args.seed)
        print(json.dumps(res.row(), indent=1))
        return

    cfg = get_model_config(args.arch)
    if not args.full_config:
        cfg = reduced(cfg)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    run = RunConfig(
        model=cfg, shape=shape,
        horn=HornConfig(enabled=not args.no_horn,
                        num_groups=args.horn_groups),
        topology=TopologyConfig(kind=args.topology),
        optimizer=args.optimizer, learning_rate=args.lr, seed=args.seed)
    mesh = make_test_mesh(args.mesh_data, args.mesh_model)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}  "
          f"arch: {cfg.name}  params: {cfg.param_count():,}")

    step_fn, shardings = S.make_train_step(run, mesh)
    state = jax.jit(lambda k: S.init_state(k, run),
                    out_shardings=shardings["state"])(jax.random.key(args.seed))

    pipe = SyntheticTokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed))

    def batch_at(step: int):
        b = pipe.batch_at(step)
        extra = {}
        if cfg.is_encoder_decoder:
            extra["frames"] = np.zeros((args.batch, cfg.encoder_seq,
                                        cfg.d_model), np.float32)
        if cfg.num_patches:
            extra["patch_embeds"] = np.zeros(
                (args.batch, cfg.num_patches, cfg.d_model), np.float32)
            b = {k: v[:, : args.seq - cfg.num_patches] for k, v in b.items()}
        return {**b, **extra}

    t0 = time.time()
    losses = []

    def on_metrics(step, metrics):
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            dt = time.time() - t0
            tok = step * args.batch * args.seq / max(dt, 1e-9)
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"grad_norm {float(metrics['grad_norm']):.3f} "
                  f"{tok:,.0f} tok/s")

    if args.checkpoint_dir:
        ck = Checkpointer(args.checkpoint_dir)
        if ck.latest_step() is not None:
            state, at = ck.restore(state, shardings=shardings["state"])
            print(f"resumed from step {at}")
        state, last, reason = fault_tolerant_loop(
            state=state, step_fn=step_fn, batch_at=batch_at,
            checkpointer=ck, num_steps=args.steps,
            checkpoint_every=args.checkpoint_every,
            state_shardings=shardings["state"], on_metrics=on_metrics)
        print(f"exit: {reason} at step {last}")
    else:
        for step in range(args.steps):
            state, metrics = step_fn(state, batch_at(step))
            on_metrics(step + 1, metrics)
    if losses:
        print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f} "
              f"({'improved' if losses[-1] < losses[0] else 'NOT improved'})")


if __name__ == "__main__":
    main()
