"""Serving launcher: batched greedy decoding.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --prompts 4 --new-tokens 16 [--full-size] \
      [--overlap-mode ficco_autotune]

The model is the reduced variant (2 layers, float32) unless
``--full-size`` asks for the published config (bfloat16).

``--overlap-mode ficco_autotune`` selects TP overlap schedules through
the persistent runtime autotuner (repro.autotune) — serving processes
restart often, so tuned decisions surviving on disk is exactly what the
cache is for.

``--adapt`` additionally runs the online-adaptation tier
(:mod:`repro.serve.adapt`): a bounded in-memory decision cache over the
persistent store, a background re-fit thread, and the
exploration-budget measured tier.  Knobs: ``--adapt-cache-size``,
``--adapt-ttl``, ``--adapt-refit-s``, ``--adapt-explore-rate``.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.cache import use_compile_cache
from repro.models.model import build_model
from repro.serve.engine import DecodeEngine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--full-size", action="store_true",
                    help="serve the published config (bfloat16) instead "
                    "of the reduced variant")
    ap.add_argument(
        "--overlap-mode", default="gspmd_serial",
        help="gspmd_serial | serial | shard_p2p | ficco_auto | "
        "ficco_autotune | explicit schedule value",
    )
    ap.add_argument(
        "--adapt", action="store_true",
        help="enable the online-adaptation tier (repro.serve.adapt)",
    )
    ap.add_argument("--adapt-cache-size", type=int, default=4096,
                    help="in-memory decision cache bound (LRU beyond)")
    ap.add_argument("--adapt-ttl", type=float, default=300.0,
                    help="decision TTL seconds (expiry forces a re-rank)")
    ap.add_argument("--adapt-refit-s", type=float, default=2.0,
                    help="background re-fit cadence seconds")
    ap.add_argument("--adapt-explore-rate", type=float, default=1.0,
                    help="measured-tier token-bucket refill (sessions/s)")
    ap.add_argument("--adapt-no-sentinel", action="store_true",
                    help="disable the drift sentinel (repro.obs.sentinel)")
    ap.add_argument("--signatures", metavar="PATH", default=None,
                    help="stream per-decision inefficiency signatures to "
                    "this JSONL path (repro.obs.signature)")
    args = ap.parse_args()

    if args.signatures:
        from repro.obs import signature as _signature

        _signature.enable_signatures(args.signatures)

    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    if args.overlap_mode != "gspmd_serial":
        cfg = dataclasses.replace(
            cfg,
            overlap=dataclasses.replace(cfg.overlap, mode=args.overlap_mode),
        )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    enc_len = 16 if cfg.encdec else 0
    tier = None
    if args.adapt:
        from repro.serve.adapt import AdaptConfig, AdaptiveTier

        tier = AdaptiveTier(
            config=AdaptConfig(
                cache_size=args.adapt_cache_size,
                ttl_s=args.adapt_ttl,
                refit_interval_s=args.adapt_refit_s,
                explore_rate=args.adapt_explore_rate,
                sentinel=not args.adapt_no_sentinel,
            ),
        ).start()
    eng = DecodeEngine(
        cfg, params, batch_size=args.prompts, cache_len=args.cache_len,
        enc_len=enc_len, adapt=tier,
    )
    if cfg.encdec:
        import jax.numpy as jnp

        frames = jnp.zeros((args.prompts, enc_len, cfg.d_model))
        eng.cache = model.prefill_cross(params, eng.cache, frames)
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new_tokens=args.new_tokens,
        )
        for _ in range(args.prompts)
    ]
    t0 = time.time()
    out = eng.run(reqs)
    dt = time.time() - t0
    total = sum(len(r.out) for r in out)
    dev = jax.devices()[0]
    print(f"decoded {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s on {dev.platform} {dev.device_kind}, "
          f"compile included)")
    if tier is not None:
        dec = eng.last_decision
        sched = dec.schedule.value if dec is not None else "-"
        print(f"adapt: schedule={sched} stats={tier.stats()}")
        tier.stop()
    if args.signatures:
        from repro.obs import signature as _signature

        stream = _signature.get_signatures()
        if stream is not None:
            snap = stream.export_jsonl()
            print(
                f"signatures: {len(snap['cells'])} cells "
                f"-> {args.signatures}"
            )
    for i, r in enumerate(out):
        print(f"req{i}: {list(r.prompt)} -> {r.out}")


if __name__ == "__main__":
    main()
