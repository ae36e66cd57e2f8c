"""Quickstart: the paper's contribution in 30 lines.

1. Pick a data-dependent AG->GEMM scenario (Table I),
2. let the FiCCO heuristic choose a bespoke overlap schedule,
3. compare the full design space with the batched simulator — on the
   NumPy engine or the jit-compiled JAX engine (``--backend jax``),
4. run the numerically-exact schedule on this host's devices.

Run:  PYTHONPATH=src python examples/quickstart.py [--backend jax|numpy]
      [--machine mi300x-8|tpu-v5e-axis16] [--schedule auto|autotune]
"""

import argparse
import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (
    MACHINES, SCENARIOS, engine_names, explore_grid, select_schedule,
)
from repro.overlap import ficco_linear

ap = argparse.ArgumentParser(description=__doc__)
ap.add_argument("--backend", choices=engine_names(), default="numpy",
                help="grid engine from the repro.core.engine registry")
ap.add_argument("--machine", choices=sorted(MACHINES), default="mi300x-8")
ap.add_argument("--schedule", choices=("auto", "autotune"), default="auto",
                help="auto: static heuristic; autotune: cached runtime tuner")
args = ap.parse_args()
machine = MACHINES[args.machine]

scenario = SCENARIOS["g9"]  # llama-3-405b QKV projection under SP+TP
print(f"scenario {scenario.name}: GEMM {scenario.gemm} "
      f"({scenario.parallelism}, {scenario.model})")

# --- 1+2: static heuristic pick (paper Fig. 12a + learned serial gate) --
dec = select_schedule(scenario.gemm, machine)
print(f"heuristic -> {dec.schedule.value}   ({dec.reason})")

# --- 3: full design-space exploration on the chosen backend ------------
ex = explore_grid([scenario], machines=[machine], backend=args.backend)
grid = ex.grid
order = np.argsort(np.where(grid.valid[:, 0, 0], grid.total[:, 0, 0],
                            np.inf))
print(f"ranking on {machine.name} via the {args.backend} engine:")
for l in order:
    if not grid.valid[l, 0, 0]:
        continue
    sched = grid.schedules[int(l)]
    mark = " <- heuristic" if sched is dec.schedule else ""
    print(f"  {sched.value:20s} speedup {grid.speedup[l, 0, 0]:5.2f}x{mark}")

# --- 4: execute the schedule exactly (8 simulated devices) -------------
mesh = jax.make_mesh((8,), ("tp",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)  # M-sharded
w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)  # N-sharded

fn = jax.jit(
    jax.shard_map(
        functools.partial(
            ficco_linear, axis_name="tp", schedule=args.schedule,
            machine=machine,
        ),
        mesh=mesh,
        in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"),
        check_vma=False,
    )
)
out = fn(x, w)
np.testing.assert_allclose(
    np.asarray(out), np.asarray(x @ w), rtol=1e-3, atol=1e-3
)
print(f"ficco_linear({args.schedule}) == serial oracle: OK  "
      f"(out {out.shape})")
