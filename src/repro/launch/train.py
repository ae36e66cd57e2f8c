"""Training launcher.

Two modes:
  * --reduced (default): actually train the reduced variant on this host
    for a few hundred steps — the end-to-end driver (deliverable b).
  * --dry-run: delegate to launch.dryrun for the production mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 200 [--overlap-mode ficco_auto|ficco_autotune] \
      [--ckpt-dir /tmp/ckpt]

``--overlap-mode ficco_autotune`` routes every TP linear's schedule pick
through the persistent runtime autotuner (repro.autotune): the first
process pays microseconds per distinct GEMM shape for the jitted analytic
model, every later run starts from the on-disk cache.
"""

from __future__ import annotations

import argparse
import dataclasses

from repro.configs import ARCHS, get_config
from repro.configs.base import ShapeConfig
from repro.launch.cache import use_compile_cache
from repro.train.loop import train
from repro.train.optimizer import OptimizerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument(
        "--overlap-mode", default="gspmd_serial",
        help="gspmd_serial | serial | shard_p2p | ficco_auto | "
        "ficco_autotune | explicit schedule value",
    )
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full (non-reduced) config — host-memory "
                    "bound; intended for cluster runs")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    if args.overlap_mode != "gspmd_serial":
        cfg = dataclasses.replace(
            cfg,
            overlap=dataclasses.replace(cfg.overlap, mode=args.overlap_mode),
        )
    shape = ShapeConfig("cli", args.seq_len, args.batch, "train")
    ocfg = OptimizerConfig(
        peak_lr=args.lr,
        warmup_steps=max(args.steps // 20, 5),
        decay_steps=args.steps,
    )
    res = train(
        cfg,
        shape,
        steps=args.steps,
        ocfg=ocfg,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
    )
    first, last = res["history"][0]["loss"], res["history"][-1]["loss"]
    print(f"done: loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
