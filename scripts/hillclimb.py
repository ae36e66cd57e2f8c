"""§Perf hillclimbing driver: hypothesis -> change -> measure -> validate.

Runs a named sequence of config variants through the dry-run for the three
chosen (arch x shape) pairs and records the roofline deltas.  Each variant
carries an explicit hypothesis string; the JSON output is the §Perf log's
source of truth.

Usage:
  PYTHONPATH=src python scripts/hillclimb.py --pair yi_train \
      --json results/hillclimb_yi_train.json
"""

import argparse
import dataclasses
import json
import sys

sys.path.insert(0, "src")

from repro.launch.dryrun import dryrun_one  # noqa: E402  (sets XLA_FLAGS)
from repro.configs.base import OverlapConfig  # noqa: E402


def _analytic_prepass(arch: str, shape_name: str) -> None:
    """Batched FiCCO pre-pass: before burning minutes in XLA dry-runs,
    sweep the pair's data-dependent AG->GEMMs through the vectorized
    design-space engine (one ``explore_grid`` call) and print the
    predicted best schedule + speedup per GEMM on the production mesh."""
    from repro.configs import SHAPES, get_config
    from repro.core import TPU_V5E
    from repro.core.explorer import explore_grid
    from repro.core.workload import tp_gemms, tp_token_rows

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    m = tp_token_rows(shape.global_batch, shape.seq_len)
    gemms = tp_gemms(cfg, m)
    ex = explore_grid(list(gemms.values()), machines=(TPU_V5E,))
    best_idx = ex.best_idx
    best_total = ex.grid.best_total()
    print(f"##### analytic prepass: {arch} x {shape_name} (g=16, v5e)")
    for i, name in enumerate(gemms):
        best = ex.grid.schedules[int(best_idx[i, 0])]
        pick = ex.grid.schedules[int(ex.heuristic_idx[i, 0])]
        sp = ex.grid.serial_total[i, 0] / best_total[i, 0]
        print(
            f"  {name:14s} best={best.value:18s} {sp:4.2f}x "
            f"heuristic={pick.value}"
        )


def _overlap(mode):
    def t(cfg):
        return dataclasses.replace(cfg, overlap=OverlapConfig(mode=mode))

    return t


def _no_remat(cfg):
    return dataclasses.replace(cfg, remat=False)


def _remat_dots(cfg):
    return dataclasses.replace(cfg, remat_policy="dots")


def _sm_decode(cfg):
    return dataclasses.replace(
        cfg, overlap=dataclasses.replace(cfg.overlap, decode_attn="shard_map")
    )


def _window(w):
    def t(cfg):
        return dataclasses.replace(cfg, sliding_window=w)

    return t


def _no_fsdp(cfg):
    return cfg  # handled via monkeypatch below


PAIRS = {
    # (1) Most representative of the paper's technique AND most
    # collective-bound train pair: DeepSeek EP (Table I g13 is DeepSeek!).
    # Baseline roofline: compute 455ms / memory 134ms / COLLECTIVE 711ms.
    "deepseek_train": {
        "arch": "deepseek-v2-lite-16b",
        "shape": "train_4k",
        "variants": [
            ("baseline_gspmd_serial", None,
             "Baseline: collective-dominated (MoE dispatch all-to-alls + "
             "MLA TP collectives): t_coll 711ms > t_compute 455ms."),
            ("ficco_auto", {"overlap": "ficco_auto"},
             "HYPOTHESIS (paper-faithful FiCCO): shared-expert/TP MLP "
             "AG->GEMMs run heuristic FiCCO schedules -> chunked "
             "all-gathers (count x16, each 1/16 size) XLA can pipeline; "
             "total collective bytes ~unchanged, exposure structurally "
             "reduced."),
            ("accum4", {"accum_steps": 4},
             "HYPOTHESIS (beyond-paper): 4-way grad-accumulation cuts live "
             "dispatch/activation buffers ~4x (315GiB/dev is unusable); "
             "collective bytes unchanged (same tokens), memory/device "
             "must drop several-fold."),
            ("no_remat", _no_remat,
             "HYPOTHESIS: dropping remat removes the recomputed forward "
             "(~25% of compute term) but inflates live activations; for "
             "this memory-stressed pair that is the wrong direction — "
             "expect refutation as a useful negative result."),
            ("remat_dots", _remat_dots,
             "HYPOTHESIS (from no_remat finding: remat re-runs the "
             "collectives, 711->473ms without it): dots_saveable keeps "
             "GEMM outputs so the backward skips GEMM+collective "
             "recompute — collective term should approach the no_remat "
             "473ms at far less memory than no_remat's 3.1TiB."),
            ("ficco_accum4", {"overlap": "ficco_auto", "accum_steps": 4},
             "COMBINED best: paper technique + microbatching."),
        ],
    },
    # (2) Most collective-bound decode pair: yi-9b decode_32k
    # (coll fraction 0.89: context-sharded KV cache reductions).
    "yi_decode": {
        "arch": "yi-9b",
        "shape": "decode_32k",
        "variants": [
            ("baseline", None,
             "Baseline: KV cache time-sharded over model axis -> "
             "attention partials all-reduced every step."),
            ("cache_batch_only", "CACHE_BATCH_ONLY",
             "HYPOTHESIS: batch-only cache sharding removes the "
             "context-parallel reduction collectives entirely "
             "(collective term down ~10x) at ~16x per-device cache bytes "
             "(10.8 -> ~170GiB... expect memory to explode: trade-off "
             "quantified)."),
            ("ficco_auto", {"overlap": "ficco_auto"},
             "HYPOTHESIS: decode-step GEMMs (128 rows) are below the "
             "decomposition guard -> FiCCO correctly stays serial; "
             "no regression."),
            ("weights_no_fsdp", "WEIGHTS_NO_FSDP",
             "HYPOTHESIS (from baseline breakdown: 4.9GB/step of "
             "all-gathers = ZeRO-3 weight gathering, absurd for decode): "
             "replicating params over the data axis (TP-only weight "
             "sharding, +~1GiB/dev for 9B params) should remove most of "
             "the all-gather volume -> collective term down several-fold."),
            ("shard_map_flash_decode", _sm_decode,
             "HYPOTHESIS (from headdim/batch-only refutations: GSPMD "
             "cannot keep the scores->softmax->AV chain distributed): "
             "an EXPLICIT shard_map flash-decode — local partial softmax "
             "+ pmax/psum of (B,H)-sized statistics — removes the K/V "
             "gathers entirely: collective bytes should drop from "
             "~4.6GB/step to MB-scale psums (the same explicit-"
             "decomposition move FiCCO makes for GEMMs)."),
            ("headdim_cache", "CACHE_HEADDIM",
             "HYPOTHESIS: sharding the KV cache on head_dim (128/16=8) "
             "instead of the 32k time axis makes the in-place cache "
             "update shard-local and turns attention into a cheap "
             "partial-sum all-reduce of (B,H,1,S) scores instead of "
             "gathering K/V slices."),
        ],
    },
    # (3) Worst-fit pair: jamba train (1052 GiB/device temp — activations
    # of 72 layers x 8192 width + MoE dispatch far beyond HBM).
    "jamba_train": {
        "arch": "jamba-1.5-large-398b",
        "shape": "train_4k",
        "variants": [
            ("baseline", None,
             "Baseline: memory catastrophically over HBM (1052 GiB/dev)."),
            ("accum4", {"accum_steps": 4},
             "HYPOTHESIS: 4-way microbatching divides live activations "
             "~4x; compute/collective terms unchanged (same total work)."),
            ("accum8", {"accum_steps": 8},
             "HYPOTHESIS: 8-way halves memory again vs accum4 with "
             "diminishing returns once weights+moments dominate."),
        ],
    },
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", choices=sorted(PAIRS), required=True)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    spec = PAIRS[args.pair]

    _analytic_prepass(spec["arch"], spec["shape"])

    results = []
    for name, transform, hypothesis in spec["variants"]:
        print(f"\n##### variant {name}: {hypothesis}\n", flush=True)
        kw = {}
        t = transform
        if isinstance(transform, dict):
            kw = dict(transform)
            t = None
        undo = None
        if transform == "CACHE_BATCH_ONLY":
            # monkeypatch the cache sharding rule for this variant
            from repro.parallel import sharding as shmod
            from jax.sharding import PartitionSpec as P

            orig = shmod.cache_leaf_spec

            def batch_only(shape, mesh, **kw):
                sp = orig(shape, mesh, **kw)
                entries = [
                    e if (isinstance(e, tuple) and "model" not in e)
                    or (e != "model")
                    else None
                    for e in sp
                ]
                return P(*entries)

            shmod.cache_leaf_spec = batch_only
            undo = lambda: setattr(shmod, "cache_leaf_spec", orig)
            t = None
        elif transform == "CACHE_HEADDIM":
            from repro.parallel import sharding as shmod
            from jax.sharding import PartitionSpec as P

            orig = shmod.cache_leaf_spec

            def headdim(shape, mesh, **kw):
                model = mesh.shape.get("model", 1)
                if len(shape) == 5 and shape[-1] % model == 0:
                    # (periods, S, KV, B, hd): batch + head_dim sharding
                    sp = list(orig(shape, mesh, **kw))
                    sp += [None] * (5 - len(sp))
                    sp[1] = None  # drop time-axis sharding
                    sp[4] = "model"
                    return P(*sp)
                return orig(shape, mesh, **kw)

            shmod.cache_leaf_spec = headdim
            undo = lambda: setattr(shmod, "cache_leaf_spec", orig)
            t = None
        elif transform == "WEIGHTS_NO_FSDP":
            from repro.parallel import sharding as shmod

            orig_fix = shmod.fix_param_spec

            def no_fsdp(spec, shape, mesh, *, fsdp_axis="data"):
                return orig_fix(spec, shape, mesh, fsdp_axis="__none__")

            shmod.fix_param_spec = no_fsdp
            undo = lambda: setattr(shmod, "fix_param_spec", orig_fix)
            t = None
        try:
            overlap = kw.pop("overlap", "gspmd_serial")
            r = dryrun_one(
                spec["arch"], spec["shape"],
                overlap=overlap,
                transform=t,
                extrapolate=True,
                **kw,
            )
        except Exception as e:
            import traceback

            traceback.print_exc()
            r = {"ok": False, "error": str(e)}
        finally:
            if undo is not None:
                undo()
        r["variant"] = name
        r["hypothesis"] = hypothesis
        results.append(r)

    if args.json:
        json.dump(results, open(args.json, "w"), indent=1)
    print("\n===== summary =====")
    for r in results:
        if not r.get("ok"):
            print(f"{r['variant']}: FAILED {r.get('error','')[:80]}")
            continue
        print(
            f"{r['variant']:24s} compute={r['t_compute']*1e3:9.2f}ms "
            f"memory={r['t_memory']*1e3:8.2f}ms "
            f"collective={r['t_collective']*1e3:8.2f}ms "
            f"mem/dev={r['bytes_per_device']/2**30:6.2f}GiB "
            f"AGs={r['collective_counts'].get('all-gather', 0) + r['collective_counts'].get('all-gather-start', 0)}"
        )


if __name__ == "__main__":
    main()
