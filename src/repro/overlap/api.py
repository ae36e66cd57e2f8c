"""Public overlap API: heuristic-driven bespoke schedules (paper §VI-A).

"To incorporate FiCCO, the user provides only the GEMM inputs; based on the
GEMM dimensions our heuristic will select and execute the optimum overlap
schedule, replacing the serial communication and computation."

``ficco_linear`` is that entry point for JAX: call it *inside* a shard_map
whose ``axis_name`` is the tensor-parallel group.  ``schedule="auto"``
consults :func:`repro.core.heuristics.select_schedule` with the *static*
global GEMM dimensions — no profiling — and dispatches the chosen schedule.
``schedule="autotune"`` goes one step further: it consults the process-wide
:class:`repro.autotune.Autotuner` (persistent cache -> jitted analytic
model -> optional measured shortlist).  When the tuner's model cannot rank
the shape, the tuner itself answers with the static heuristic and the
resolution is counted as ``overlap/resolve.autotune_fallback``.
"""

from __future__ import annotations

from typing import Union

import jax
from jax import lax

from repro.core.heuristics import select_schedule
from repro.core.machine import TPU_V5E, MachineSpec, machine_for_group
from repro.core.schedule_types import Schedule
from repro.core.workload import GemmShape
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.overlap.schedules import SCHEDULE_FNS, run_schedule

ScheduleLike = Union[Schedule, str]


def resolve_schedule(
    schedule: ScheduleLike,
    *,
    m: int,
    n: int,
    k: int,
    machine: MachineSpec | None = None,
    dtype_bytes: int = 2,
    group: int | None = None,
) -> Schedule:
    """Static schedule resolution (trace-time: shapes are concrete).

    ``group`` is the actual overlap-axis size; the decision tree (and in
    particular its group-sensitive serial gate) is evaluated against the
    machine model retargeted at that group, not the model's default.
    """
    def _resolved(how: str, sched: Schedule, sp) -> Schedule:
        _metrics.get_metrics().counter(f"overlap/resolve.{how}").inc()
        sp.set(how=how, schedule=sched.value)
        return sched

    with _trace.span(
        "overlap/resolve", "overlap", m=m, n=n, k=k, group=group,
    ) as sp:
        if isinstance(schedule, Schedule):
            return _resolved("explicit", schedule, sp)
        eff = machine or TPU_V5E
        if group:
            eff = machine_for_group(eff, group)
        if schedule == "autotune":
            from repro.autotune import get_tuner  # keep import lazy

            dec = get_tuner().pick(
                GemmShape(m, n, k, dtype_bytes), machine, group=group
            )
            # The tuner answers "heuristic" when its model could not rank
            # the shape; that decision is the static tree's, so count it.
            fallback = dec.source == "heuristic"
            how = "autotune_fallback" if fallback else "autotune"
            return _resolved(how, dec.schedule, sp)
        if schedule != "auto":
            return _resolved("named", Schedule(schedule), sp)
        dec = select_schedule(GemmShape(m, n, k, dtype_bytes), eff)
        # The serial guard may also fire for shapes the schedules can't chunk.
        return _resolved("auto", dec.schedule, sp)


def _divisible(m_s: int, k: int, g: int, sched: Schedule) -> bool:
    if sched in (Schedule.SERIAL,):
        return True
    if sched is Schedule.UNIFORM_FUSED_2D:
        return k % g == 0
    if sched is Schedule.SHARD_P2P:
        return True
    return m_s % g == 0  # 1D FiCCO chunks rows one level deeper


def ficco_linear(
    x: jax.Array,
    w: jax.Array,
    *,
    axis_name: str,
    schedule: ScheduleLike = "auto",
    machine: MachineSpec | None = None,
) -> jax.Array:
    """Data-dependent AG->GEMM with a bespoke overlap schedule.

    Args:
      x: (M/g, K) row shard of the activation (inside shard_map).
      w: (K, N/g) resident column shard of the weight.
      axis_name: mesh axis of the TP group.
      schedule: explicit :class:`Schedule`, its string value, "auto"
        (static heuristic) or "autotune" (cached/analytic runtime tuner).

    Returns:
      (M, N/g): the full gathered-M rows times this device's weight columns.
    """
    g = lax.axis_size(axis_name)
    m_s, k = x.shape
    n_local = w.shape[1]
    sched = resolve_schedule(
        schedule,
        m=m_s * g,
        n=n_local * g,
        k=k,
        machine=machine,
        dtype_bytes=x.dtype.itemsize,
        group=g,
    )
    if not _divisible(m_s, k, g, sched):
        sched = Schedule.SERIAL  # shape can't be chunked one level deeper
    return run_schedule(sched, x, w, axis_name=axis_name)


__all__ = [
    "Schedule",
    "SCHEDULE_FNS",
    "ficco_linear",
    "resolve_schedule",
    "run_schedule",
]
