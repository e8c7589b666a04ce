"""Training launcher.

Single-host smoke scale by default; with multiple local devices (e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the same driver
runs the sharded production step (DP×TP mesh, optional FSDP) — the code
path is identical to the multi-pod deployment, only the mesh differs.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --smoke --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced (CPU-sized) config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--grad-compression", action="store_true",
                    help="int8 error-feedback gradient compression "
                         "(repro.dist.compression)")
    ap.add_argument("--mesh", default="host",
                    help="host (no mesh) | testN (N local devices)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    from repro.configs import get_config, get_smoke_config
    from repro.models import build_model
    from repro.sharding import rules
    from repro.train import Trainer, TrainConfig
    from repro.train.data import DataConfig, make_dataset
    from repro.train.optimizer import AdamWConfig

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    tc = TrainConfig(
        steps=args.steps, grad_accum=args.grad_accum, remat=args.remat,
        log_every=args.log_every, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, grad_compression=args.grad_compression,
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                              total_steps=args.steps))

    data = make_dataset(DataConfig(batch=args.batch, seq_len=args.seq,
                                   vocab_size=cfg.vocab_size))

    if args.mesh == "host":
        trainer = Trainer(model, tc)
        t0 = time.perf_counter()
        out = trainer.train(data)
        dt = time.perf_counter() - t0
        losses = [h["loss"] for h in out["history"]]
        print(f"[train] {cfg.name}: {out['final_step']} steps in {dt:.1f}s "
              f"loss {losses[0]:.4f} → {losses[-1]:.4f} "
              f"stragglers={len(out['straggler_events'])}")
        return

    # sharded path: same step function under a mesh
    from repro.launch.dryrun import _mesh
    mesh = _mesh(args.mesh)
    from repro.launch import steps as S
    state_shapes = S.train_state_specs(model,
                                       compression=args.grad_compression)
    with mesh:
        state_sh = rules.state_shardings(state_shapes, mesh, fsdp=args.fsdp)
        fn = S.train_step_fn(model, grad_accum=args.grad_accum,
                             remat=args.remat,
                             compression=args.grad_compression)
        step_fn = jax.jit(fn, in_shardings=(state_sh, None),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,))
        from repro.train.trainer import init_state
        from repro.train.optimizer import adamw
        state = jax.device_put(
            init_state(model, jax.random.PRNGKey(0), adamw(tc.optimizer),
                       compression=args.grad_compression),
            state_sh)
        it = iter(data)
        t0 = time.perf_counter()
        for i in range(args.steps):
            batch = jax.tree.map(jnp.asarray, next(it))
            state, metrics = step_fn(state, batch)
            if (i + 1) % args.log_every == 0:
                print(f"[train/mesh] step {i+1} loss "
                      f"{float(metrics['loss']):.4f}")
        jax.block_until_ready(state)
        print(f"[train/mesh] {args.steps} steps on {mesh.devices.size} devices "
              f"in {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
