"""Compile each cell's timed step for a described TPU v5e, at full size.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]

Nothing runs and no chip is needed: the TPU compiler, which is installed,
compiles for a ``v5e:2x2`` that libtpu describes, and refuses what the
chip would refuse (a program that does not fit, a kernel it cannot
lower).  For each cell it prints one JSON line with the compiled step's
``memory_analysis()`` in bytes, per device.  Run it by hand before a chip
call; it is not part of the test suite.
"""

from __future__ import annotations

import functools
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {k: int(getattr(ma, k)) for k in keys}
    out["total_gb"] = (out["argument_size_in_bytes"]
                       + out["output_size_in_bytes"]
                       - out["alias_size_in_bytes"]
                       + out["temp_size_in_bytes"]) / 1e9
    return out


def _sds(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def rehearse(cell: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from chipbench import harness, weights
    from chipbench.model import Dims, program_config
    from repro.models.model import build_model

    c = harness.Files(ROOT).cell(cell)
    cfg, mix = c["config"], c["traffic"]
    m = Dims.from_config(cfg)
    model = build_model(program_config(cfg))
    shapes = jax.eval_shape(functools.partial(weights.program_params, m),
                            jax.random.PRNGKey(0))
    one = SingleDeviceSharding(topo.devices[0])
    loop = mix["loop"]
    if loop == "train":
        from repro.train import optimizer as opt
        from repro.train.loop import make_train_step

        state = jax.eval_shape(
            lambda p: {"params": p, "opt_state": opt.init_state(p)}, shapes)
        toks = jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]),
                                    jnp.int32, sharding=one)
        step = jax.jit(make_train_step(
            model, opt.OptimizerConfig(**cfg["optimizer"])),
            donate_argnums=0)
        compiled = step.lower(_sds(state, one),
                              {"tokens": toks, "labels": toks}).compile()
    elif loop == "decode":
        from repro.serve.engine import make_serve_step

        cache = jax.eval_shape(
            lambda: model.init_cache(mix["batch"], mix["cache_len"]))
        toks = jax.ShapeDtypeStruct((mix["batch"], 1), jnp.int32,
                                    sharding=one)
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        compiled = jax.jit(make_serve_step(model)).lower(
            _sds(shapes, one), _sds(cache, one), toks, pos).compile()
    else:
        import repro.kernels.ops as ops
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import fix_param_specs
        from repro.serve.engine import make_prefill

        # The kernels pick interpret mode from the default backend (the
        # CPU here); the described chip needs them compiled.
        ops._interpret = lambda: False
        axes = cfg["mesh"]
        n = 1
        for v in axes.values():
            n *= v
        mesh = make_mesh(tuple(axes.values()), tuple(axes),
                         devices=topo.devices[:n])
        specs = fix_param_specs(model.param_specs(), shapes, mesh)
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            shapes, specs)
        toks = jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]),
                                    jnp.int32,
                                    sharding=NamedSharding(mesh, P()))
        with jax.sharding.set_mesh(mesh):
            compiled = jax.jit(make_prefill(model)).lower(
                params, {"tokens": toks}).compile()
        text = compiled.as_text()
        return {"cell": cell, "memory": _memory(compiled),
                "tpu_custom_call": text.count("tpu_custom_call"),
                "all_gather": text.count("all-gather"),
                "reduce_scatter": text.count("reduce-scatter")}
    return {"cell": cell, "memory": _memory(compiled)}


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from chipbench import harness

    cells = (argv if argv else sys.argv[1:]) or [
        w["name"] for w in harness.Files(ROOT).spec()["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for cell in cells:
        print(json.dumps(rehearse(cell, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
