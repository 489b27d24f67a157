"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --steps 50 --loss heat --ckpt-dir /tmp/run1
    PYTHONPATH=src python -m repro.launch.train --mf --steps 500   # paper model

On a real TPU pod this process runs once per host (jax.distributed) and the
mesh comes from ``--mesh production``; on CPU use ``--mesh host`` (default).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax


def main():
    """CLI entry: train the LM (or the paper's CF model with --mf)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--mf", action="store_true", help="train the paper's CF model")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--loss", default="heat", choices=["heat", "softmax"])
    ap.add_argument("--remat", default="none", choices=["full", "none"])
    ap.add_argument("--optimizer", default="adamw",
                    choices=["sgd", "adamw", "adafactor"])
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--steps-per-dispatch", type=int, default=16,
                    help="K>1 scans K training steps per XLA dispatch "
                         "(device-resident EpochExecutor; losses sync at "
                         "window edges). 1 = per-step dispatch loop.")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "data", "production"],
                    help="host: --mesh-data x --mesh-model devices; data: "
                         "pure data-parallel over every visible device "
                         "(e.g. XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 on a laptop/CI box); production: the TPU "
                         "pod topology")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--backend", default=None,
                    help="loss backend (engine.LOSS_IMPLS): fused, autodiff, "
                         "simplex_bmm, mse_dot, pallas — applies to the MF "
                         "engine and the LM HEAT head alike")
    ap.add_argument("--update-impl", default=None,
                    help="MF row-update impl: scatter_add, pallas, dense")
    ap.add_argument("--sampler", default=None,
                    choices=["auto", "uniform", "tile", "popularity",
                             "in_batch"],
                    help="negative-sampling strategy (engine.SAMPLERS, "
                         "default: auto)")
    ap.add_argument("--table-format", default=None,
                    choices=["fp32", "int8"],
                    help="MF embedding-table storage: fp32 (default) or "
                         "int8 + per-row scales with stochastic-rounded "
                         "updates (optim/quantization.py)")
    args = ap.parse_args()

    from repro.distributed import sharding as shd
    from repro.launch.mesh import (make_data_mesh, make_host_mesh,
                                   make_production_mesh)

    mesh = (make_production_mesh() if args.mesh == "production"
            else make_data_mesh() if args.mesh == "data"
            else make_host_mesh(args.mesh_data, args.mesh_model))

    with shd.use_mesh(mesh if mesh.size > 1 else None):
        if args.mf:
            from repro.configs.heat_mf import MF_100M
            from repro.core.engine import resolve_engine
            from repro.data import pipeline
            from repro.train import trainer
            cfg = MF_100M if not args.reduced else dataclasses.replace(
                MF_100M, num_users=2000, num_items=4000, emb_dim=64)
            overrides = {k: v for k, v in (
                ("backend", args.backend), ("update_impl", args.update_impl),
                ("sampler", args.sampler),
                ("table_format", args.table_format)) if v}
            if overrides:
                cfg = dataclasses.replace(cfg, **overrides)
            engine = resolve_engine(cfg)
            print(f"[launch] MF engine: {engine.name} "
                  f"(steps_per_dispatch={args.steps_per_dispatch}, "
                  f"devices={mesh.size if mesh.size > 1 else 1})")
            ds = pipeline.synth_cf_dataset(min(cfg.num_users, 4096),
                                           cfg.num_items)
            state, losses = trainer.train_mf(
                cfg, ds, steps=args.steps, batch_size=args.batch,
                engine=engine,
                steps_per_dispatch=args.steps_per_dispatch,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                fail_at_step=args.fail_at_step)
        else:
            from repro.configs import get_config
            from repro.models import lm
            from repro.train import trainer
            cfg = get_config(args.arch)
            if args.reduced:
                cfg = cfg.reduced()
            # The LM HEAT head resolves from the same registries as the MF
            # engine: --backend / --sampler select its loss and strategy too.
            heat_over = {k: v for k, v in (
                ("backend", args.backend), ("sampler", args.sampler)) if v}
            if heat_over:
                cfg = dataclasses.replace(
                    cfg, heat=dataclasses.replace(cfg.heat, **heat_over))
            if args.loss == "heat":
                from repro.core.engine import resolve_engine
                print("[launch] LM head engine: "
                      f"{resolve_engine(cfg.heat).name}")
            opts = lm.TrainOptions(loss=args.loss, remat=args.remat,
                                   attn_chunk=min(1024, args.seq))
            tcfg = trainer.TrainerConfig(
                steps=args.steps, lr=args.lr, batch_size=args.batch,
                seq_len=args.seq, optimizer=args.optimizer,
                grad_accum=args.grad_accum, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, fail_at_step=args.fail_at_step,
                steps_per_dispatch=args.steps_per_dispatch)
            extras = None
            if cfg.family == "audio":
                extras = {"frames": ((args.batch, cfg.encoder_seq, cfg.d_model),
                                     jax.numpy.float32)}
            if cfg.family == "vlm":
                extras = {"patches": ((args.batch, cfg.num_patches, cfg.d_model),
                                      jax.numpy.float32)}
            state, losses = trainer.train_lm(cfg, opts, tcfg, extras_spec=extras)
        print(f"done: {len(losses)} steps, final loss {losses[-1]:.4f}")


if __name__ == "__main__":
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    main()
