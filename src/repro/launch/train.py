"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 100 --batch 8 --seq 128 [--mesh host]

``--smoke`` trains the reduced config of the chosen architecture (CPU
friendly); without it the full published config is used (requires real
accelerators). ``--mesh host`` builds a mesh over the visible devices and
runs the fully-sharded (FSDP x TP) step.
"""
from __future__ import annotations

import argparse

from repro.configs import ARCH_IDS, get_config, reduce_for_smoke
from repro.data import SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.train import Trainer, TrainerConfig
from repro.train.train_step import StepConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_launch_train")
    ap.add_argument("--mesh", default="none", choices=["none", "host"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    model = get_model(cfg)
    print(f"[train] {cfg.name}: {cfg.param_count() / 1e6:.1f}M params")

    mesh = None
    if args.mesh == "host":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model_axis=1)

    ds = SyntheticLMDataset(cfg, global_batch=args.batch, seq_len=args.seq,
                            seed=args.seed)
    trainer = Trainer(
        model, ds,
        TrainerConfig(total_steps=args.steps, checkpoint_every=max(args.steps // 2, 1),
                      checkpoint_dir=args.ckpt, log_every=10, seed=args.seed),
        StepConfig(peak_lr=args.lr, warmup_steps=min(30, args.steps // 3),
                   total_steps=args.steps, microbatches=args.microbatches),
        mesh=mesh)
    res = trainer.run()
    print(f"[train] done: final loss {res['losses'][-1]:.4f} "
          f"(start {res['losses'][0]:.4f})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
