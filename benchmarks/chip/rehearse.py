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


def rehearse(cell: str, topo) -> dict:
    """The cell's program, as its loop builds it, compiled for ``topo``:
    a mesh of its devices, and arguments without a sharding of their own
    on its first device."""
    import jax
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.ops as ops
    from chipbench import harness, scopes

    # The kernels pick interpret mode from the default backend (the CPU
    # here); the described chip needs them compiled.
    ops._interpret = lambda: False
    built = scopes.program(harness.Files(ROOT), cell, topo.devices)
    if built is None:
        return {"cell": cell, "program": None}
    fn, args, mesh = built
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree.map(
        lambda a: a if a.sharding is not None else jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), args)
    if mesh is None:
        compiled = fn.lower(*args).compile()
    else:
        with jax.sharding.set_mesh(mesh):
            compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    return {"cell": cell, "memory": _memory(compiled),
            "tpu_custom_call": text.count("tpu_custom_call"),
            "all_gather": text.count("all-gather"),
            "reduce_scatter": text.count("reduce-scatter")}


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
