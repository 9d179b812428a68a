"""Training driver (runnable end-to-end on this CPU host).

    PYTHONPATH=src python -m repro.launch.train \
        --arch yi-6b --reduced --steps 40 --batch 8 --seq 128 \
        --ckpt-dir /tmp/ckpt --ckpt-every 10 [--resume] [--kill-at 25]

Production posture: sharded params (logical-axis rules over the host
mesh), AdamW + ZeRO-1, deterministic resumable data stream, atomic
checkpoints, straggler/heartbeat hooks (train/fault.py). `--kill-at N`
simulates a mid-run failure; re-running with --resume picks up from the
newest COMMITTED checkpoint and reproduces the same batch stream.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced as reduce_cfg
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.train.checkpoint import (latest_step, prune_checkpoints,
                                    restore_checkpoint, save_checkpoint)
from repro.train.data import DataConfig, batches
from repro.train.optimizer import AdamWConfig, init_adamw
from repro.train.train_step import TrainConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="simulate a host failure after N steps")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = Model(cfg, remat=True)
    print(f"arch={cfg.name} params={model.param_count() / 1e6:.1f}M")

    ocfg = AdamWConfig(lr_peak=args.lr, warmup_steps=5,
                       total_steps=args.steps)
    tcfg = TrainConfig(microbatches=args.microbatches, optimizer=ocfg)
    train_step = jax.jit(make_train_step(model, tcfg),
                         donate_argnums=(0, 1))

    params = model.init(jax.random.PRNGKey(0))
    opt_state = init_adamw(params)
    start = 0
    if args.resume and latest_step(args.ckpt_dir) is not None:
        (params, opt_state), start = restore_checkpoint(
            args.ckpt_dir, (params, opt_state))
        print(f"resumed from step {start}")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    stream = batches(dcfg, start_step=start)

    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
        params, opt_state, metrics = train_step(params, opt_state, batch)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"({(time.time() - t0) / max(1, step - start + 1):.2f}s/it)",
                  flush=True)
        if (step + 1) % args.ckpt_every == 0:
            path = save_checkpoint(args.ckpt_dir, step + 1,
                                   (params, opt_state))
            prune_checkpoints(args.ckpt_dir, keep=3)
            print(f"checkpointed -> {path}")
        if args.kill_at is not None and step + 1 >= args.kill_at:
            print(f"simulated failure at step {step + 1} "
                  f"(restart with --resume)")
            raise SystemExit(42)
    print(f"done: {args.steps} steps in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
