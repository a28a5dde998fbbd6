"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch grok-1-314b --reduced \
        --steps 50 --moe-backend mixnet --reconfig-every 8

Full-size configs target the production mesh (run under real TPU slices or
the dry-run); ``--reduced`` trains the same-family smoke config on whatever
devices exist, with the complete runtime (MixNet control loop, checkpoints,
watchdog) active.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.data.pipeline import FileLM, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import make_plan
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="grok-1-314b")
    ap.add_argument("--reduced", action="store_true",
                    help="same-family smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data", default="", help="path for byte-level FileLM")
    ap.add_argument("--moe-backend", choices=("einsum", "mixnet"), default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reconfig-every", type=int, default=0,
                    help="MixNet runtime reconfiguration cadence (0=off)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.moe_backend and cfg.is_moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, backend=args.moe_backend)
        )

    devices = jax.devices()
    mesh = None
    if len(devices) > 1:
        # Largest (data, model) factorization available.
        n = len(devices)
        model = 1
        for m in (16, 8, 4, 2):
            if n % m == 0:
                model = m
                break
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((n // model, model), ("data", "model"))
    plan = make_plan(mesh)

    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps * 2,
                      moment_dtype=cfg.opt_moment_dtype)
    tcfg = TrainerConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir or f"/tmp/repro_ckpt_{cfg.name}",
        reconfig_every=args.reconfig_every,
    )
    trainer = Trainer(cfg, opt, tcfg, plan, mesh=mesh, seed=args.seed)
    if args.resume and trainer.maybe_restore():
        print(f"resumed from step {trainer.step}")

    if args.data:
        data = FileLM(args.data, args.seq_len, args.batch, vocab_size=cfg.vocab_size)
    else:
        data = SyntheticLM(cfg.vocab_size, args.seq_len, args.batch, seed=args.seed)

    print(f"{cfg.name}: {cfg.param_count()/1e6:.1f}M params on "
          f"{len(devices)} device(s), mesh={mesh and mesh.devices.shape}")
    log = trainer.train(iter(data))
    losses = [float(m["loss"]) for m in log]
    print(f"steps {trainer.step}: loss {np.mean(losses[:3]):.3f} -> "
          f"{np.mean(losses[-3:]):.3f}; reconfigs={trainer.reconfig_count}; "
          f"stragglers={trainer.straggler_events}")


if __name__ == "__main__":
    main()
