import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh).

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices build the production meshes, every
step function is lowered from ShapeDtypeStructs (no allocation), compiled
through full SPMD partitioning, and the compiled artifact yields the
memory analysis + the three roofline terms (repro.roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k [--multi-pod] [--overlap-mode ficco_auto] \
      [--json out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all  # full 10x4 matrix
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.launch import specs as specmod  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.parallel.context import overlap_context  # noqa: E402
from repro.parallel.sharding import (  # noqa: E402
    BATCH_AXES,
    cache_specs,
    filter_pspec,
    fix_param_specs,
)
from repro.roofline import analysis as roofline  # noqa: E402
from repro.roofline import counters  # noqa: E402
from repro.train import optimizer as opt  # noqa: E402
from repro.train.loop import make_train_step  # noqa: E402

# Full-attention families run long_500k via their sliding-window variant
# (DESIGN.md §5); SSM/hybrid run it natively.
LONG_CONTEXT_WINDOW = 8192


def prepared_config(arch: str, shape: ShapeConfig, overlap: str) -> ModelConfig:
    cfg = get_config(arch)
    if shape.name == "long_500k" and cfg.family.value in (
        "dense", "moe", "vlm", "audio"
    ):
        cfg = dataclasses.replace(cfg, sliding_window=LONG_CONTEXT_WINDOW)
    if overlap != "gspmd_serial":
        cfg = dataclasses.replace(
            cfg,
            overlap=dataclasses.replace(cfg.overlap, mode=overlap),
        )
    return cfg


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, filter_pspec(sp, mesh)),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _batch_specs(batch_shapes, mesh):
    def leaf(l):
        b = l.shape[0]
        dp = 1
        axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
        for a in axes:
            dp *= mesh.shape[a]
        if b % dp == 0 and dp > 1:
            return P(axes, *([None] * (len(l.shape) - 1)))
        return P(*([None] * len(l.shape)))

    return jax.tree.map(leaf, batch_shapes)


def _build_jitted(cfg, shape, mesh, accum_steps: int = 1):
    """(jitted, abstract_args) for the step function of this shape kind."""
    model = build_model(cfg)
    param_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))
    )
    pspecs = fix_param_specs(model.param_specs(), param_shapes, mesh)
    big = (
        sum(
            float(jnp.prod(jnp.array(l.shape)))
            for l in jax.tree.leaves(param_shapes)
        )
        > 1e11
    )
    if True:
        if shape.kind == "train":
            ocfg = opt.OptimizerConfig(
                moment_dtype="bfloat16" if big else "float32"
            )
            state_shapes = {
                "params": param_shapes,
                "opt_state": jax.eval_shape(
                    lambda: opt.init_state(param_shapes, ocfg.moment_dtype)
                ),
            }
            state_specs = {
                "params": pspecs,
                "opt_state": opt.state_specs(pspecs),
            }
            batch_shapes = specmod.train_specs(cfg, shape)
            bspecs = _batch_specs(batch_shapes, mesh)
            fn = make_train_step(model, ocfg, accum_steps=accum_steps)
            jitted = jax.jit(
                fn,
                in_shardings=(
                    _named(mesh, state_specs), _named(mesh, bspecs)
                ),
            )
            args = (state_shapes, batch_shapes)
        elif shape.kind == "prefill":
            batch_shapes = specmod.train_specs(cfg, shape)
            bspecs = _batch_specs(batch_shapes, mesh)

            def fwd(params, batch):
                with overlap_context(cfg.overlap):
                    logits, _ = model.forward(params, batch)
                return logits

            jitted = jax.jit(
                fwd,
                in_shardings=(
                    _named(mesh, pspecs), _named(mesh, bspecs)
                ),
            )
            args = (param_shapes, batch_shapes)
        else:  # decode
            dspec = specmod.decode_specs(cfg, shape, model)
            cspecs = cache_specs(dspec["cache"], mesh)
            tspec = _batch_specs({"tokens": dspec["tokens"]}, mesh)["tokens"]

            def serve_step(params, cache, tokens, pos):
                with overlap_context(cfg.overlap):
                    return model.decode_step(params, cache, tokens, pos)

            jitted = jax.jit(
                serve_step,
                in_shardings=(
                    _named(mesh, pspecs),
                    _named(mesh, cspecs),
                    NamedSharding(mesh, filter_pspec(tspec, mesh)),
                    NamedSharding(mesh, P()),
                ),
            )
            args = (
                param_shapes, dspec["cache"], dspec["tokens"], dspec["pos"]
            )
    return jitted, args, cfg


def _compile(cfg, shape, mesh):
    jitted, args, _ = _build_jitted(cfg, shape, mesh)
    with jax.sharding.set_mesh(mesh):
        with overlap_context(cfg.overlap):
            lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def _cost_triple(compiled):
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", ca.get("bytes_accessed", 0.0)))
    coll = roofline.parse_collectives(compiled.as_text())
    return flops, nbytes, coll.total_bytes


def extrapolated_collectives(cfg, shape, mesh):
    """Collective bytes corrected for the layer scan: compile UNROLLED
    1-period and 2-period variants, take the per-period delta, scale to
    full depth (collectives never live inside time scans; see counters).
    Returns (collective_bytes, hlo_flops_extrap, hlo_bytes_extrap)."""
    period = len(
        __import__("repro.models.model", fromlist=["layer_pattern"])
        .layer_pattern(cfg)
    )
    n_periods = cfg.num_layers // period
    if n_periods < 2:
        c = _compile(cfg, shape, mesh)
        return _cost_triple(c)[2], None, None
    enc = cfg.encdec
    mk = lambda k: dataclasses.replace(
        cfg,
        num_layers=k * period,
        scan_layers=False,
        encdec=dataclasses.replace(
            enc, encoder_layers=max(1, k * enc.encoder_layers // n_periods)
        )
        if enc
        else None,
    )
    f1, b1, c1 = _cost_triple(_compile(mk(1), shape, mesh))
    f2, b2, c2 = _cost_triple(_compile(mk(2), shape, mesh))
    body = (f2 - f1, b2 - b1, c2 - c1)
    out = (2 * f1 - f2, 2 * b1 - b2, 2 * c1 - c2)
    total = tuple(
        max(o + bd * n_periods, 0.0) for o, bd in zip(out, body)
    )
    return total[2], total[0], total[1]


def dryrun_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    overlap: str = "gspmd_serial",
    verbose: bool = True,
    extrapolate: bool = True,
    transform=None,
    accum_steps: int = 1,
) -> dict:
    shape = SHAPES[shape_name]
    cfg = prepared_config(arch, shape, overlap)
    if transform is not None:
        cfg = transform(cfg)  # hillclimb config overrides (§Perf)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    jitted, args, _ = _build_jitted(cfg, shape, mesh, accum_steps)
    with jax.sharding.set_mesh(mesh):
        with overlap_context(cfg.overlap):
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    rf = roofline.analyze(
        arch=arch,
        shape=shape_name,
        mesh_name="2x16x16" if multi_pod else "16x16",
        chips=chips,
        compiled=compiled,
        model_flops=roofline.model_flops_for(cfg, shape, shape.kind),
    )
    raw = {
        "raw_hlo_flops": rf.hlo_flops,
        "raw_hlo_bytes": rf.hlo_bytes,
        "raw_collective_bytes": rf.collective_bytes,
    }
    # Analytic compute/memory terms (XLA cost_analysis counts scan bodies
    # once — see repro.roofline.counters) + depth-extrapolated collectives.
    ana = counters.step_costs(cfg, shape, shape.kind)
    rf.hlo_flops = ana.flops
    rf.hlo_bytes = ana.bytes
    if extrapolate:
        try:
            coll, _, _ = extrapolated_collectives(cfg, shape, mesh)
            rf.collective_bytes = coll
        except Exception:
            traceback.print_exc()
            raw["extrapolation_failed"] = True
    result = rf.to_dict()
    result.update(raw)
    result.update(
        overlap=overlap,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        ok=True,
    )
    if verbose:
        print(f"== {arch} x {shape_name} ({result['mesh']}, {overlap}) ==")
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        print(
            f"cost: flops={result['hlo_flops']:.3e} "
            f"bytes={result['hlo_bytes']:.3e} "
            f"collective_bytes={result['collective_bytes']:.3e}"
        )
        print(
            f"roofline: compute={rf.t_compute*1e3:.2f}ms "
            f"memory={rf.t_memory*1e3:.2f}ms "
            f"collective={rf.t_collective*1e3:.2f}ms "
            f"dominant={rf.dominant} "
            f"useful={rf.useful_flops_ratio:.2f}"
        )
        print(f"lower {t_lower:.0f}s compile {t_compile:.0f}s")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--overlap-mode", default="gspmd_serial")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--no-extrapolate", action="store_true",
                    help="skip the unrolled-variant compiles (multi-pod "
                    "sweep: pass/fail + memory only; roofline is single-pod)")
    args = ap.parse_args()

    runs = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in SHAPES:
                runs.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        runs.append((args.arch, args.shape))

    results = []
    for arch, shape in runs:
        try:
            results.append(
                dryrun_one(
                    arch, shape,
                    multi_pod=args.multi_pod,
                    overlap=args.overlap_mode,
                    extrapolate=not args.no_extrapolate,
                )
            )
        except Exception as e:
            traceback.print_exc()
            results.append(
                {"arch": arch, "shape": shape, "ok": False, "error": str(e)}
            )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if not r.get("ok")]
    print(f"\n{len(results) - len(bad)}/{len(results)} dry-runs passed")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
