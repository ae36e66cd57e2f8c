"""Tensor-sequence-parallel linears with FiCCO overlap (paper Fig. 3).

``tp_ficco_linear`` is the production integration point: activations enter
sequence-sharded over the ``model`` axis (Megatron sequence parallelism),
the weight is column-sharded, and the data-dependent AG->GEMM is executed
by a bespoke FiCCO schedule chosen from the static GEMM dims (Fig. 12a) —
exactly the paper's drop-in replacement for serial collective+GEMM.

Modes (config.overlap.mode):
  * "gspmd_serial" — not handled here; plain constraints, XLA collectives.
  * "serial" / "shard_p2p" / "ficco_auto" / "ficco_autotune" / explicit
    schedule value — shard_map with the corresponding schedule from
    repro.overlap ("ficco_autotune" consults the persistent runtime
    tuner in repro.autotune, falling back to the static heuristic).
Backend "pallas_dma" swaps the chunk exchange for the Pallas ICI-DMA
kernel (repro.kernels) — the paper's DMA offload made explicit.  Each
such linear counts the path that served it (``tp/pallas_dma.dma`` or
``tp/pallas_dma.xla``), so a run can assert the kernel really ran.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import OverlapConfig
from repro.core.machine import TPU_V5E
from repro.core.schedule_types import Schedule
from repro.obs import metrics as _metrics
from repro.overlap.api import ficco_linear
from repro.parallel.sharding import BATCH_AXES, MODEL_AXIS, _active_mesh


def _mode_to_schedule(mode: str):
    if mode == "ficco_auto":
        return "auto"
    if mode == "ficco_autotune":
        return "autotune"
    return mode  # Schedule enum value string or "serial"/"shard_p2p"


def overlap_applicable(x: jax.Array, w: jax.Array) -> bool:
    mesh = _active_mesh()
    if mesh is None or MODEL_AXIS not in mesh.shape:
        return False
    g = mesh.shape[MODEL_AXIS]
    if g <= 1:
        return False
    b, s, d = x.shape
    return s % g == 0 and w.shape[1] % g == 0


def tp_ficco_linear(
    x: jax.Array,
    w: jax.Array,
    overlap: OverlapConfig,
) -> jax.Array:
    """x: (B, S, D) -> (B, S, F) with FiCCO-overlapped AG->GEMM.

    The activation is constrained sequence-sharded over ``model`` (the
    tensor-sequence-parallel start state of paper Fig. 3a); inside the
    shard_map each device holds (B_local, S/g, D) and computes the full-S
    x (F/g) output block via the selected schedule.
    """
    mesh = _active_mesh()
    g = mesh.shape[MODEL_AXIS]
    b, s, d = x.shape
    f = w.shape[1]
    schedule = _mode_to_schedule(overlap.mode)

    def body(x_shard, w_shard):
        # (B_local, S/g, D) -> rows ordered seq-major so the all-gather's
        # device-major concatenation reconstructs the global seq order.
        b_local = x_shard.shape[0]
        rows = x_shard.transpose(1, 0, 2).reshape(-1, d)  # (S/g*B, D)
        use_dma = schedule in (
            "auto", Schedule.UNIFORM_FUSED_1D.value
        ) and rows.shape[0] % g == 0
        if overlap.backend == "pallas_dma":
            # Trace-time count of which path served each DMA-backend
            # linear: a schedule or shape the kernel cannot take runs the
            # XLA collectives instead.
            path = "dma" if use_dma else "xla"
            _metrics.get_metrics().counter(f"tp/pallas_dma.{path}").inc()
        if overlap.backend == "pallas_dma" and use_dma:
            from repro.kernels.ops import ag_matmul_dma

            out = ag_matmul_dma(rows, w_shard, axis_name=MODEL_AXIS)
        else:
            out = ficco_linear(
                rows,
                w_shard,
                axis_name=MODEL_AXIS,
                schedule=schedule,
                machine=TPU_V5E,
            )
        # out: (S * B_local, F/g) -> (B_local, S, F/g)
        return out.reshape(s, b_local, f // g).transpose(1, 0, 2)

    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    bspec = batch_axes if batch_axes else None
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(bspec, MODEL_AXIS, None), P(None, MODEL_AXIS)),
        out_specs=P(bspec, None, MODEL_AXIS),
        check_vma=False,
    )(x, w)
