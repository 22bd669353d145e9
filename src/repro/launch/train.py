"""Production training launcher.

On a real TPU cluster each host runs this under its own process (with
jax.distributed.initialize); here it drives the same code single-process.
For the 512-placeholder-device mesh use launch/dryrun.py — this launcher
executes real steps and therefore uses the actual local devices.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --smoke --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax

from repro.data.pipeline import DataConfig, TokenPipeline
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models.registry import get_config
from repro.optim.adamw import AdamWConfig
from repro.optim.schedules import warmup_cosine
from repro.train.trainer import TrainConfig, Trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", default=None, choices=[None, "bf16", "int8"])
    ap.add_argument("--quant", default=None,
                    choices=[None, "off", "ternary", "cim", "cim_fused"])
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.quant:
        import dataclasses

        cfg = cfg.replace(quant=dataclasses.replace(cfg.quant, mode=args.quant))
    print(f"[train] {cfg.name}: {cfg.param_count():,} params, "
          f"quant={cfg.quant.mode}, devices={len(jax.devices())}")

    pipe = TokenPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))
    opt = AdamWConfig(lr=args.lr, schedule=warmup_cosine(20, args.steps))
    tcfg = TrainConfig(
        num_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, log_every=10,
        grad_compression=args.grad_compression,
    )
    trainer = Trainer(cfg, opt, tcfg, pipe)
    log = trainer.run()
    print(f"[train] done: loss {log[0]['loss']:.4f} -> {log[-1]['loss']:.4f}; "
          f"restarts={trainer.restarts} stragglers={len(trainer.straggler_steps)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
