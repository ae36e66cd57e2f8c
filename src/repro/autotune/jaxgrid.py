"""On-accelerator batched FiCCO grid engine (jit + vmap + grad).

This is the ``jax.numpy`` port of ``repro.core.batch``: the roofline GEMM
model, the communication model, the CIL formulas and the two-channel
pipeline scan, all expressed as pure array math over a
``(schedule, scenario, machine)`` grid so that

  * the whole sweep compiles to one XLA program (``jax.jit``), vmapped
    over the machine axis — sweeps can run *on-accelerator* inside a
    framework scheduling loop;
  * every output is differentiable w.r.t. the machine parameters and the
    heuristic threshold horizon TAU, which turns threshold calibration
    into a few Adam steps (:func:`calibrate_tau`) instead of a discrete
    candidate search.

Numerics: the engine runs in float64 (``jax.enable_x64``
scoped to this module's entry points — the global x64 flag is never
touched) and replays the NumPy engine's accumulation order, so grids
agree with ``repro.core.batch.evaluate_grid`` to ~1e-12 relative, far
inside the 1e-5 acceptance tolerance.  The kernels are additionally
dtype-generic over the :class:`MachineArrays` float leaves: packing
them at float32/bfloat16 (``machine_arrays(..., dtype=...)``) evaluates
the whole grid at that precision with float64 confined to the pipeline
scan's accumulator — the ``"mixed"`` engine (``repro.sweep.device``)
builds on exactly this, and the float64 default is bit-identical to the
pre-dtype-generic code.

Machines with different group sizes vmap together by padding every
pipeline to ``g_max`` steps; padded steps carry zero time and a masked
dependency, which leaves totals, busy times and exposed time bit-exact.

Quick start (the whole grid on-accelerator in three lines)::

    from repro.autotune import evaluate_grid
    grid = evaluate_grid(scenarios, machines, backend="jax")
    best = grid.best_idx()
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import inefficiency as ineff
from repro.core.batch import (
    GRID_SCHEDULES,
    GridResult,
    _as_batch,
    _as_ragged_batch,
)
from repro.core.heuristics import MIN_DECOMPOSE_FLOPS
from repro.core.machine import MachineSpec, Topology
from repro.core.schedule_types import STUDIED, Schedule

_F = jnp.float64
_I = jnp.int64


class MachineArrays(NamedTuple):
    """Struct-of-arrays pytree of M machines (leading axis M).

    The calibrated coefficients (``s_half``, the four CIL coefficients,
    ``mt_ref``) are solved host-side by the NumPy bisections in
    ``repro.core.inefficiency`` — exactly the values the NumPy engine
    uses — and enter the jitted program as ordinary differentiable
    leaves.
    """

    peak_flops: jax.Array
    hbm_bw: jax.Array
    link_bw: jax.Array
    group: jax.Array  # int
    is_mesh: jax.Array  # bool: FULL_MESH vs TORUS_RING/SWITCH
    p2p_links: jax.Array  # int
    a2a_links: jax.Array  # int
    kernel_latency: jax.Array
    link_latency: jax.Array
    tile_mn: jax.Array  # int
    tile_k: jax.Array  # int
    parallel_units: jax.Array  # int
    kernel_ramp: jax.Array
    s_half: jax.Array
    cil_gemm_c2: jax.Array
    cil_gemm_c3: jax.Array
    cil_comm_c2: jax.Array
    cil_comm_c3: jax.Array
    mt_ref: jax.Array


def machine_arrays(machines, *, dtype=None) -> MachineArrays:
    """Pack MachineSpecs (plus their host-calibrated coefficients).

    ``dtype`` sets the float leaves' dtype (default float64) — the
    kernels below derive their compute dtype from the machine leaves, so
    packing at float32/bfloat16 is how the mixed-precision engine
    (``repro.sweep.device``) selects its evaluation precision without a
    second code path.  Integer/bool leaves are dtype-invariant.
    """
    ms = tuple(machines)
    fdt = _F if dtype is None else jnp.dtype(dtype)

    def fa(get):  # float leaf
        return jnp.asarray([get(m) for m in ms], dtype=fdt)

    def ia(get):  # int leaf
        return jnp.asarray([get(m) for m in ms], dtype=_I)

    return MachineArrays(
        peak_flops=fa(lambda m: m.peak_flops),
        hbm_bw=fa(lambda m: m.hbm_bw),
        link_bw=fa(lambda m: m.link_bw),
        group=ia(lambda m: m.group),
        is_mesh=jnp.asarray(
            [m.topology is Topology.FULL_MESH for m in ms], dtype=bool
        ),
        p2p_links=ia(lambda m: m.p2p_links),
        a2a_links=ia(lambda m: m.a2a_links),
        kernel_latency=fa(lambda m: m.kernel_latency),
        link_latency=fa(lambda m: m.link_latency),
        tile_mn=ia(lambda m: m.tile_mn),
        tile_k=ia(lambda m: m.tile_k),
        parallel_units=ia(lambda m: m.parallel_units),
        kernel_ramp=fa(lambda m: m.kernel_ramp),
        s_half=fa(ineff.calibrated_s_half),
        cil_gemm_c2=fa(lambda m: ineff._cil_coeff(m, "gemm", 2)),
        cil_gemm_c3=fa(lambda m: ineff._cil_coeff(m, "gemm", 3)),
        cil_comm_c2=fa(lambda m: ineff._cil_coeff(m, "comm", 2)),
        cil_comm_c3=fa(lambda m: ineff._cil_coeff(m, "comm", 3)),
        mt_ref=fa(ineff._mt_ref),
    )


def scenario_arrays(scenarios) -> tuple[jax.Array, ...]:
    """(m, n, k, dtype_bytes) int64 device arrays from any scenario form."""
    sb = _as_batch(scenarios)
    return (
        jnp.asarray(sb.m, dtype=_I),
        jnp.asarray(sb.n, dtype=_I),
        jnp.asarray(sb.k, dtype=_I),
        jnp.asarray(sb.dtype_bytes, dtype=_I),
    )


# ---------------------------------------------------------------------------
# Roofline GEMM model (port of batch.gemm_exec_vec).
# ---------------------------------------------------------------------------


def _floor_div(a, b):
    """Exact int64 floor-div via float division.

    Scalar 64-bit integer division costs ~30 cycles per lane on CPU and
    never vectorizes; float division is SIMD.  The substitution is
    *exact* — not approximate — whenever ``quotient * b < 2**53`` (f64):
    a correctly-rounded quotient then sits strictly inside the 1/b gap
    around the true rational, so its floor equals the integer result.
    Every shape field here is far smaller (m <= 2**21, n, k <= 2**16,
    tile counts <= 2**26), with the same argument holding even for an
    f32 fallback (< 2**24) if a caller traces outside the x64 scope.
    """
    af = jnp.asarray(a).astype(jnp.float64)
    bf = jnp.asarray(b).astype(jnp.float64)
    return jnp.floor(af / bf).astype(jnp.int64)


def gemm_exec_jax(m, n, k, b, mp: MachineArrays, *, accumulate=False):
    """Elementwise roofline GEMM time; mirrors ``batch.gemm_exec_vec``.

    The compute dtype follows the machine leaves (float64 by default;
    float32/bfloat16 when :func:`machine_arrays` packed them that way).
    The explicit casts below pin the integer->float promotion points:
    without them, jax promotes python-scalar x int64 products to the
    default float, silently re-widening a mixed-precision program.  In
    float64 every cast is exact for the representable shape ranges, so
    the default path is unchanged bit-for-bit.
    """
    dt = mp.peak_flops.dtype
    t_mn, pu = mp.tile_mn, mp.parallel_units
    # >= 1 tile even for sub-row ragged chunks (see batch.gemm_exec_vec).
    cm = jnp.maximum(_floor_div(m + t_mn - 1, t_mn), 1)
    cn = jnp.maximum(_floor_div(n + t_mn - 1, t_mn), 1)
    tiles = cm * cn
    split_cap = jnp.where(m <= t_mn, 2, 8)
    ceil_pu = _floor_div(pu + tiles - 1, jnp.maximum(tiles, 1))
    splits = jnp.minimum(
        jnp.minimum(ceil_pu, jnp.maximum(_floor_div(k, mp.tile_k), 1)),
        split_cap,
    )
    splits = jnp.where(tiles < pu, splits, 1)
    work = tiles * splits
    padded_flops = 2.0 * ((cm * t_mn) * (cn * t_mn)).astype(dt) * k.astype(dt)
    occ_quant = work.astype(dt) / ((-_floor_div(-work, pu)) * pu).astype(dt)
    occ_smooth = jnp.minimum(1.0, work.astype(dt) / pu)
    occupancy = 0.5 * (occ_quant + occ_smooth)
    k_eff = k.astype(dt) / (k + mp.tile_k).astype(dt)
    compute = (
        padded_flops / mp.peak_flops / jnp.maximum(occupancy * k_eff, 1e-9)
    )
    bytes_hbm = (m * k + k * n + m * n).astype(dt) * b
    if accumulate:
        bytes_hbm = bytes_hbm + (m * n).astype(dt) * b
    bytes_hbm = bytes_hbm + jnp.where(
        splits > 1,
        2.0 * (splits - 1).astype(dt) * (m * n).astype(dt) * 4,
        0.0,
    )
    memory = bytes_hbm / mp.hbm_bw
    base = jnp.maximum(compute, memory)
    ramp = mp.kernel_ramp
    t = mp.kernel_latency + base * (1.0 + ramp / (base + ramp))
    return jnp.where(m > 0, t, jnp.nan)


# ---------------------------------------------------------------------------
# Communication model.
# ---------------------------------------------------------------------------


def comm_time_jax(nbytes_per_link, mp: MachineArrays, *, n_transfers=1):
    per = nbytes_per_link / jnp.maximum(n_transfers, 1)
    t_one = mp.link_latency + (per + mp.s_half) / mp.link_bw
    return n_transfers * t_one


def ag_serial_time_jax(mk_bytes, mp: MachineArrays):
    g = mp.group
    per_link = jnp.where(
        mp.is_mesh,
        mk_bytes / g,
        mk_bytes * (g - 1) / g / mp.a2a_links,
    )
    return comm_time_jax(per_link, mp)


def p2p_step_time_jax(shard_bytes, mp: MachineArrays):
    return comm_time_jax(shard_bytes / mp.p2p_links, mp)


def a2a_chunk_step_time_jax(chunk_bytes, mp: MachineArrays):
    g = mp.group
    per_link = jnp.where(
        mp.is_mesh, chunk_bytes, chunk_bytes * (g - 1) / mp.a2a_links
    )
    n = jnp.where(mp.is_mesh, 1, jnp.maximum((g - 1) // mp.a2a_links, 1))
    return comm_time_jax(per_link, mp, n_transfers=n)


def hbm_move_time_jax(nbytes, mp: MachineArrays):
    return mp.kernel_latency + 2.0 * nbytes / mp.hbm_bw


# ---------------------------------------------------------------------------
# CIL formulas.
# ---------------------------------------------------------------------------


def _mt_norm_jax(m, n, k, b, mp: MachineArrays):
    bytes_mt = (m * k + k * n + m * n).astype(mp.mt_ref.dtype) * b
    return bytes_mt / mp.mt_ref


def _cil_jax(mt_p, c2, c3, *, degree: int, dma: bool, rccl_extra):
    c = c2 if min(max(degree, 2), 3) == 2 else c3
    cil = 1.0 + c * (min(degree, 3) - 1) * mt_p
    if degree > 3:
        cil = cil * (1.0 + 0.02 * (degree - 3))
    if not dma:
        cil = cil + rccl_extra
    return cil


def gemm_cil_jax(m, n, k, b, mp, *, degree: int, dma: bool = True):
    mt_p = _mt_norm_jax(m, n, k, b, mp) ** 0.5
    return _cil_jax(
        mt_p, mp.cil_gemm_c2, mp.cil_gemm_c3, degree=degree, dma=dma,
        rccl_extra=ineff.RCCL_EXTRA_GEMM_CIL * mt_p + 0.15,
    )


def comm_cil_jax(m, n, k, b, mp, *, degree: int, dma: bool = True):
    mt_p = _mt_norm_jax(m, n, k, b, mp) ** 0.5
    return _cil_jax(
        mt_p, mp.cil_comm_c2, mp.cil_comm_c3, degree=degree, dma=dma,
        rccl_extra=0.10,
    )


# ---------------------------------------------------------------------------
# Pipeline recurrence, padded to g_max steps.
# ---------------------------------------------------------------------------


def pipeline_jax(comm_steps, compute_steps, deps, comm_active, comp_active):
    """Two-channel pipeline over padded step lists.

    ``comm_steps`` / ``compute_steps`` are length-``g_max``(+1) lists of
    per-scenario time arrays; ``*_active`` are matching boolean masks
    (scalars or arrays) marking real steps.  Inactive steps add exactly
    0.0 time and never stall, so a group-g machine inside a
    group-``g_max`` padded scan reproduces the unpadded recurrence
    bit-for-bit.

    The scan always **accumulates in float64**, whatever dtype the step
    times arrive in: the recurrence sums ~``g_max`` terms and compares
    running channel clocks, where low-precision cancellation would turn
    stall detection into noise.  This is the mixed-precision engine's
    accumulator contract — bf16/f32 kernels, f64 pipeline — and a no-op
    for the default float64 path.
    """
    finish = []
    t = None
    for c, a in zip(comm_steps, comm_active):
        c = jnp.where(a, c, 0.0).astype(_F)
        t = c if t is None else t + c
        finish.append(t)
    zero = jnp.zeros_like(compute_steps[0], dtype=_F)
    t_comp = zero
    exposed = zero
    comp_sum = None
    for i, w in enumerate(compute_steps):
        a = comp_active[i]
        w = jnp.where(a, w, 0.0).astype(_F)
        dep = deps[i]
        if dep is not None:
            ready = finish[dep]
            stalled = a & (ready > t_comp)
            exposed = exposed + jnp.where(stalled, ready - t_comp, 0.0)
            t_comp = jnp.where(stalled, ready, t_comp)
        t_comp = t_comp + w
        comp_sum = w if comp_sum is None else comp_sum + w
    comm_sum = finish[-1] if finish else zero
    total = jnp.maximum(t_comp, comm_sum)
    return total, exposed, comm_sum, comp_sum


def pipeline_closed_jax(comm_steps, compute_steps, deps, comm_active,
                        comp_active):
    """Closed-form pipeline for *uniform* step lists (device fast path).

    Every uniform-schedule assembly in :func:`_eval_one_machine_jax`
    passes one repeated array per channel (``[t_comm] * g_max``), for
    which the scan recurrence ``t_j = max(t_{j-1}, finish_j) + w`` has
    the exact solution ``max_j (j*c + remaining_work(j))`` — linear in
    ``j``, so only the endpoint candidates matter.  That replaces
    ~``g_max`` float64 scan iterations (the dominant elementwise cost of
    a uniform grid evaluation) with a handful of ops.

    The three dep patterns assembled by ``_eval_one_machine_jax`` are
    recognised structurally:

      * ``deps[0] is None`` and one extra compute step → local-GEMM
        FiCCO (HF1D/HU1D): ``max(t_l + n*w, c + n*w, n_c*c + w)``;
      * ``deps[0] is None``, equal lengths → SHARD_P2P (first compute
        step free): ``max(n*w, n_c*c + w)``;
      * else plain FiCCO (UF2D/UF1D): ``max(c + n*w, n_c*c + w)``.

    Totals agree with :func:`pipeline_jax` to rounding only — the scan
    accumulates ``j*c`` by repeated addition, the closed form by one
    multiply — so the padded scan remains the bit-exact reference and
    this variant is opt-in (``closed_form=True``).  Ragged schedules
    (per-step distinct times) have no closed form and always scan.
    """

    def count(active):
        tot = None
        for a in active:
            v = jnp.asarray(a).astype(_F)
            tot = v if tot is None else tot + v
        return tot

    if comm_steps:
        n_c = count(comm_active)
        c = jnp.where(n_c > 0, comm_steps[0], 0.0).astype(_F)
    else:  # g_max == 1 SHARD_P2P: no inter-device steps at all
        n_c = jnp.asarray(0.0, dtype=_F)
        c = jnp.zeros_like(compute_steps[0], dtype=_F)
    comm_sum = n_c * c
    if deps[0] is None and len(compute_steps) == len(comm_steps) + 1:
        t_l = compute_steps[0].astype(_F)
        w = compute_steps[1].astype(_F)
        n_w = count(comp_active[1:])
        comp_sum = t_l + n_w * w
        t_comp = jnp.maximum(
            jnp.maximum(t_l + n_w * w, c + n_w * w), comm_sum + w
        )
    elif deps[0] is None:
        w = compute_steps[0].astype(_F)
        n_w = count(comp_active)
        comp_sum = n_w * w
        t_comp = jnp.maximum(n_w * w, comm_sum + w)
    else:
        w = compute_steps[0].astype(_F)
        n_w = count(comp_active)
        comp_sum = n_w * w
        t_comp = jnp.maximum(c + n_w * w, comm_sum + w)
    exposed = t_comp - comp_sum
    total = jnp.maximum(t_comp, comm_sum)
    return total, exposed, comm_sum, comp_sum


# ---------------------------------------------------------------------------
# Grid evaluation (one machine; vmapped over the machine axis).
# ---------------------------------------------------------------------------


def _eval_one_machine_jax(m, n, k, b, mp, g_max, schedules, dma,
                          dma_into_place, closed_form=False):
    """All schedules for one (vmapped) machine; returns (L, S) arrays.

    Kernel math runs in the machine leaves' dtype (``dt``); every output
    row is widened to float64 on the way out (``put``) so stacked
    results are homogeneous whatever precision evaluated them.

    ``closed_form=True`` swaps the padded pipeline scan for
    :func:`pipeline_closed_jax` (equal to rounding, ~2x fewer
    elementwise ops) — the device sweep fast path; the default stays the
    bit-exact scan.
    """
    pipe = pipeline_closed_jax if closed_form else pipeline_jax
    dt = mp.peak_flops.dtype
    g = mp.group
    S = m.shape[0]
    true_f = jnp.ones((S,), dtype=bool)

    n_q = _floor_div(n, g)
    dev_n = jnp.where(n == g * n_q, n_q, n)
    mk_bytes = (m * k).astype(dt) * b
    serial_comm = ag_serial_time_jax(mk_bytes, mp)
    serial_gemm = gemm_exec_jax(m, dev_n, k, b, mp)

    m_s = _floor_div(m, g)
    m_div = (m == g * m_s) & (m > 0)
    k_q = _floor_div(k, g)
    k_div = k == g * k_q
    m_sg = _floor_div(m_s, g)

    def step_active(n_steps):
        # Padded scans run g_max iterations; step s is real iff s < n_steps.
        return [s < n_steps for s in range(g_max)]

    total_rows, comm_rows, comp_rows, exp_rows = [], [], [], []
    steps_rows, valid_rows = [], []

    def put(ok, total, comm_busy, compute_busy, exposed, n_steps):
        total_rows.append(jnp.where(ok, total, jnp.nan).astype(_F))
        comm_rows.append(jnp.where(ok, comm_busy, jnp.nan).astype(_F))
        comp_rows.append(jnp.where(ok, compute_busy, jnp.nan).astype(_F))
        exp_rows.append(jnp.where(ok, exposed, jnp.nan).astype(_F))
        steps_rows.append(jnp.asarray(n_steps, dtype=_I))
        valid_rows.append(ok)

    for sched in schedules:
        if sched is Schedule.SERIAL:
            put(true_f, serial_comm + serial_gemm, serial_comm, serial_gemm,
                serial_comm, 1)
            continue

        if sched is Schedule.SHARD_P2P:
            shard_bytes = (m_s * k).astype(dt) * b
            c_cil = comm_cil_jax(m_s, dev_n, k, b, mp, degree=2, dma=dma)
            g_cil = gemm_cil_jax(m_s, dev_n, k, b, mp, degree=2, dma=dma)
            t_p2p = p2p_step_time_jax(shard_bytes, mp) * c_cil
            t_gemm = gemm_exec_jax(m_s, dev_n, k, b, mp) * g_cil
            total, exposed, comm_sum, comp_sum = pipe(
                [t_p2p] * (g_max - 1),
                [t_gemm] * g_max,
                [None] + list(range(g_max - 1)),
                step_active(g - 1),
                step_active(g),
            )
            put(m_div, total, comm_sum, comp_sum, exposed, g)
            continue

        # ---- FiCCO schedules -----------------------------------------
        if sched is Schedule.UNIFORM_FUSED_2D:
            k_g = k_q
            chunk_bytes = (m_s * k_g).astype(dt) * b
            step = (m, dev_n, k_g)
            gather_bytes = (m * k_g).astype(dt) * b
            scatter_bytes = None
            degree, accumulate = 4, True
            local = None
            per_step_gemms = jnp.asarray(1, dtype=_I)
            ok = m_div & k_div
        elif sched is Schedule.UNIFORM_FUSED_1D:
            chunk_bytes = (m_sg * k).astype(dt) * b
            step = (m_s, dev_n, k)
            gather_bytes = (m_s * k).astype(dt) * b
            scatter_bytes = (m_s * dev_n).astype(dt) * b
            degree, accumulate = 4, False
            local = None
            per_step_gemms = jnp.asarray(1, dtype=_I)
            ok = m_div
        elif sched is Schedule.HETERO_FUSED_1D:
            chunk_bytes = (m_sg * k).astype(dt) * b
            rows = (g - 1) * m_sg
            step = (rows, dev_n, k)
            gather_bytes = (rows * k).astype(dt) * b
            scatter_bytes = (rows * dev_n).astype(dt) * b
            degree, accumulate = 3, False
            local = (m_s, dev_n, k)
            per_step_gemms = jnp.asarray(1, dtype=_I)
            ok = m_div & (m_sg >= 1)
        elif sched is Schedule.HETERO_UNFUSED_1D:
            chunk_bytes = (m_sg * k).astype(dt) * b
            step = (m_sg, dev_n, k)
            gather_bytes = jnp.zeros((S,), dtype=dt)
            scatter_bytes = ((g - 1) * m_sg * dev_n).astype(dt) * b
            degree, accumulate = 2, False
            local = (m_s, dev_n, k)
            per_step_gemms = g - 1
            ok = m_div & (m_sg >= 1)
        else:  # pragma: no cover
            raise ValueError(sched)

        if dma_into_place:
            gather_bytes = jnp.zeros((S,), dtype=dt)
            scatter_bytes = None
            degree = 2
        c_cil = comm_cil_jax(m_s, dev_n, k, b, mp, degree=degree, dma=dma)
        g_cil = gemm_cil_jax(
            step[0], step[1], step[2], b, mp, degree=degree, dma=dma
        )
        t_comm = a2a_chunk_step_time_jax(chunk_bytes, mp) * c_cil
        t_gemm_step = (
            per_step_gemms
            * gemm_exec_jax(
                step[0], step[1], step[2], b, mp, accumulate=accumulate
            )
            * g_cil
        )
        t_gather = jnp.where(
            gather_bytes > 0, hbm_move_time_jax(gather_bytes, mp), 0.0
        )
        if scatter_bytes is None:
            t_scatter = jnp.zeros((S,), dtype=dt)
        else:
            t_scatter = jnp.where(
                scatter_bytes > 0,
                hbm_move_time_jax(scatter_bytes, mp),
                0.0,
            )
        t_step = jnp.maximum(t_gemm_step, t_gather + t_scatter)

        if local is not None:
            t_local = gemm_exec_jax(
                local[0], local[1], local[2], b, mp
            ) * gemm_cil_jax(
                local[0], local[1], local[2], b, mp, degree=degree, dma=dma
            )
            compute = [t_local] + [t_step] * g_max
            deps = [None] + list(range(g_max))
            comp_active = [True] + step_active(g)
        else:
            compute = [t_step] * g_max
            deps = list(range(g_max))
            comp_active = step_active(g)
        total, exposed, comm_sum, comp_sum = pipe(
            [t_comm] * g_max, compute, deps, step_active(g), comp_active
        )
        put(ok, total, comm_sum, comp_sum, exposed, g)

    return (
        jnp.stack(total_rows),
        jnp.stack(comm_rows),
        jnp.stack(comp_rows),
        jnp.stack(exp_rows),
        jnp.stack(steps_rows),
        jnp.stack(valid_rows),
        serial_comm.astype(_F),
        serial_gemm.astype(_F),
    )


# ---------------------------------------------------------------------------
# Ragged (non-uniform step) evaluation: padded (S, P) fraction matrix +
# validity masks, jit-compatible (mirrors batch.ragged_step_times).
# ---------------------------------------------------------------------------

_FICCO_SET = frozenset(STUDIED)


def ragged_step_times_jax(
    m, n, k, b, frac, mp: MachineArrays, sched: Schedule, *,
    dma: bool = True, dma_into_place: bool = False,
):
    """Per-step stream times for one (vmapped) machine; jnp twin of
    ``repro.core.batch.ragged_step_times``.

    ``frac`` is the padded ``(S, P)`` fraction matrix (static P).
    Returns ``(comm_steps, compute_steps, deps, comm_active,
    comp_active, ok)`` ready for :func:`pipeline_jax`.
    """
    if sched not in _FICCO_SET:
        raise ValueError(
            f"ragged profiles apply to the FiCCO schedules, got {sched}"
        )
    dt = mp.peak_flops.dtype
    g = mp.group
    S = m.shape[0]
    P = frac.shape[1]
    n_q = _floor_div(n, g)
    dev_n = jnp.where(n == g * n_q, n_q, n)
    m_s = _floor_div(m, g)
    m_div = (m == g * m_s) & (m > 0)
    mf = m.astype(dt)
    msf = m_s.astype(dt)
    kf = k.astype(dt)

    if sched is Schedule.UNIFORM_FUSED_2D:
        degree, accumulate = 4, True
        local = None
        per_step_gemms = jnp.asarray(1, dtype=_I)
    elif sched is Schedule.UNIFORM_FUSED_1D:
        degree, accumulate = 4, False
        local = None
        per_step_gemms = jnp.asarray(1, dtype=_I)
    elif sched is Schedule.HETERO_FUSED_1D:
        degree, accumulate = 3, False
        local = (m_s, dev_n, k)
        per_step_gemms = jnp.asarray(1, dtype=_I)
    else:  # HETERO_UNFUSED_1D
        degree, accumulate = 2, False
        local = (m_s, dev_n, k)
        per_step_gemms = g - 1
    if dma_into_place:
        degree = 2
    c_cil = comm_cil_jax(m_s, dev_n, k, b, mp, degree=degree, dma=dma)

    comm_steps, compute_steps = [], []
    comm_active, comp_active = [], []
    for s in range(P):
        f = frac[:, s]
        act = f > 0.0
        if sched is Schedule.UNIFORM_FUSED_2D:
            k_s = f * kf
            chunk_bytes = msf * k_s * b
            rows, cols, inner = mf, dev_n, k_s
            gather_bytes = mf * k_s * b
            scatter_bytes = None
        else:
            chunk_bytes = (f * msf) * kf * b
            cols, inner = dev_n, k
            if sched is Schedule.UNIFORM_FUSED_1D:
                rows = f * mf
                gather_bytes = rows * kf * b
                scatter_bytes = rows * dev_n * b
            elif sched is Schedule.HETERO_FUSED_1D:
                rows = f * ((g - 1) * msf)
                gather_bytes = rows * kf * b
                scatter_bytes = rows * dev_n * b
            else:
                rows = f * msf
                gather_bytes = None
                scatter_bytes = (g - 1) * rows * dev_n * b
        if dma_into_place:
            gather_bytes = None
            scatter_bytes = None
        t_comm = a2a_chunk_step_time_jax(chunk_bytes, mp) * c_cil
        g_cil = gemm_cil_jax(
            rows, cols, inner, b, mp, degree=degree, dma=dma
        )
        t_gemm = (
            per_step_gemms
            * gemm_exec_jax(rows, cols, inner, b, mp, accumulate=accumulate)
            * g_cil
        )
        if gather_bytes is None:
            t_gather = jnp.zeros((S,), dtype=dt)
        else:
            t_gather = jnp.where(
                gather_bytes > 0, hbm_move_time_jax(gather_bytes, mp), 0.0
            )
        if scatter_bytes is None:
            t_scatter = jnp.zeros((S,), dtype=dt)
        else:
            t_scatter = jnp.where(
                scatter_bytes > 0, hbm_move_time_jax(scatter_bytes, mp), 0.0
            )
        t_step = jnp.maximum(t_gemm, t_gather + t_scatter)
        comm_steps.append(t_comm)
        comm_active.append(act)
        compute_steps.append(t_step)
        comp_active.append(act)

    if local is not None:
        t_local = gemm_exec_jax(
            local[0], local[1], local[2], b, mp
        ) * gemm_cil_jax(
            local[0], local[1], local[2], b, mp, degree=degree, dma=dma
        )
        compute_steps = [t_local] + compute_steps
        comp_active = [jnp.ones((S,), dtype=bool)] + comp_active
        deps: list[int | None] = [None] + list(range(P))
    else:
        deps = list(range(P))
    return comm_steps, compute_steps, deps, comm_active, comp_active, m_div


def _eval_one_machine_ragged_jax(
    m, n, k, b, frac, mp, g_max, schedules, dma, dma_into_place
):
    """All schedules for one (vmapped) machine over ragged scenarios.

    SERIAL / SHARD_P2P replicate the uniform engine (profile-free); the
    FiCCO schedules run the masked ragged scan over P padded steps.
    Like the uniform evaluator, kernel math runs in the machine leaves'
    dtype and ``put`` widens every output row to float64.
    """
    dt = mp.peak_flops.dtype
    g = mp.group
    S = m.shape[0]
    P = frac.shape[1]
    true_f = jnp.ones((S,), dtype=bool)

    n_q = _floor_div(n, g)
    dev_n = jnp.where(n == g * n_q, n_q, n)
    mk_bytes = (m * k).astype(dt) * b
    serial_comm = ag_serial_time_jax(mk_bytes, mp)
    serial_gemm = gemm_exec_jax(m, dev_n, k, b, mp)

    m_s = _floor_div(m, g)
    m_div = (m == g * m_s) & (m > 0)

    def step_active(n_steps):
        return [s < n_steps for s in range(g_max)]

    total_rows, comm_rows, comp_rows, exp_rows = [], [], [], []
    steps_rows, valid_rows = [], []

    def put(ok, total, comm_busy, compute_busy, exposed, n_steps):
        total_rows.append(jnp.where(ok, total, jnp.nan).astype(_F))
        comm_rows.append(jnp.where(ok, comm_busy, jnp.nan).astype(_F))
        comp_rows.append(jnp.where(ok, compute_busy, jnp.nan).astype(_F))
        exp_rows.append(jnp.where(ok, exposed, jnp.nan).astype(_F))
        steps_rows.append(jnp.asarray(n_steps, dtype=_I))
        valid_rows.append(ok)

    for sched in schedules:
        if sched is Schedule.SERIAL:
            put(true_f, serial_comm + serial_gemm, serial_comm, serial_gemm,
                serial_comm, 1)
            continue
        if sched is Schedule.SHARD_P2P:
            shard_bytes = (m_s * k).astype(dt) * b
            c_cil = comm_cil_jax(m_s, dev_n, k, b, mp, degree=2, dma=dma)
            g_cil = gemm_cil_jax(m_s, dev_n, k, b, mp, degree=2, dma=dma)
            t_p2p = p2p_step_time_jax(shard_bytes, mp) * c_cil
            t_gemm = gemm_exec_jax(m_s, dev_n, k, b, mp) * g_cil
            total, exposed, comm_sum, comp_sum = pipeline_jax(
                [t_p2p] * (g_max - 1),
                [t_gemm] * g_max,
                [None] + list(range(g_max - 1)),
                step_active(g - 1),
                step_active(g),
            )
            put(m_div, total, comm_sum, comp_sum, exposed, g)
            continue
        comm, compute, deps, c_act, w_act, ok = ragged_step_times_jax(
            m, n, k, b, frac, mp, sched,
            dma=dma, dma_into_place=dma_into_place,
        )
        total, exposed, comm_sum, comp_sum = pipeline_jax(
            comm, compute, deps, c_act, w_act
        )
        put(ok, total, comm_sum, comp_sum, exposed, P)

    return (
        jnp.stack(total_rows),
        jnp.stack(comm_rows),
        jnp.stack(comp_rows),
        jnp.stack(exp_rows),
        jnp.stack(steps_rows),
        jnp.stack(valid_rows),
        serial_comm.astype(_F),
        serial_gemm.astype(_F),
    )


@functools.partial(
    jax.jit,
    static_argnames=("g_max", "schedules", "dma", "dma_into_place"),
)
def _ragged_grid_jit(
    m, n, k, b, frac, mp, *, g_max, schedules, dma, dma_into_place
):
    """(M-vmapped) ragged grid; outputs are (M, L, S) / (M, S) stacks."""
    return jax.vmap(
        lambda one: _eval_one_machine_ragged_jax(
            m, n, k, b, frac, one, g_max, schedules, dma, dma_into_place
        )
    )(mp)


def evaluate_ragged_grid_raw(
    scenarios,
    machines_or_arrays,
    *,
    dma: bool = True,
    dma_into_place: bool = False,
    schedules: tuple[Schedule, ...] = GRID_SCHEDULES,
    g_max: int | None = None,
):
    """Jit-evaluated ragged grid as device arrays (leading machine axis).

    ``scenarios`` is a RaggedBatch / list of RaggedScenario; the padded
    fraction matrix enters the jitted program as an ordinary operand, so
    re-running with a different skew at the same (S, P) shape costs no
    recompile.
    """
    rb = _as_ragged_batch(scenarios)
    with jax.enable_x64(True):
        if isinstance(machines_or_arrays, MachineArrays):
            mp = machines_or_arrays
            if g_max is None:
                g_max = int(np.max(np.asarray(mp.group)))
        else:
            ms = tuple(machines_or_arrays)
            mp = machine_arrays(ms)
            g_max = max(m.group for m in ms)
        m, n, k, b = scenario_arrays(rb)
        frac = jnp.asarray(rb.frac, dtype=mp.peak_flops.dtype)
        return _ragged_grid_jit(
            m, n, k, b, frac, mp,
            g_max=g_max, schedules=tuple(schedules),
            dma=dma, dma_into_place=dma_into_place,
        )


def evaluate_ragged_grid(
    scenarios,
    machines,
    *,
    dma: bool = True,
    dma_into_place: bool = False,
    schedules: tuple[Schedule, ...] = GRID_SCHEDULES,
) -> GridResult:
    """Drop-in jitted replacement for ``batch.evaluate_ragged_grid``."""
    rb = _as_ragged_batch(scenarios)
    machines = tuple(machines)
    out = evaluate_ragged_grid_raw(
        rb, machines, dma=dma, dma_into_place=dma_into_place,
        schedules=schedules,
    )
    return GridResult.from_machine_major(
        out, schedules=schedules, scenarios=rb, machines=machines, dma=dma
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "g_max", "schedules", "dma", "dma_into_place", "closed_form"
    ),
)
def _grid_jit(
    m, n, k, b, mp, *, g_max, schedules, dma, dma_into_place,
    closed_form=False,
):
    """(M-vmapped) full grid; outputs are (M, L, S) / (M, S) stacks."""
    return jax.vmap(
        lambda one: _eval_one_machine_jax(
            m, n, k, b, one, g_max, schedules, dma, dma_into_place,
            closed_form,
        )
    )(mp)


def evaluate_grid_raw(
    scenarios,
    machines_or_arrays,
    *,
    dma: bool = True,
    dma_into_place: bool = False,
    schedules: tuple[Schedule, ...] = GRID_SCHEDULES,
    g_max: int | None = None,
    closed_form: bool = False,
):
    """Jit-evaluated grid as device arrays (differentiable entry point).

    Returns ``(total, comm_busy, compute_busy, exposed, steps, valid,
    serial_comm, serial_gemm)`` with leading machine axis ``M`` —
    ``total`` is ``(M, L, S)``.  Accepts either MachineSpecs or an
    already-packed (possibly perturbed) :class:`MachineArrays`, so
    gradients w.r.t. machine parameters flow through unchanged.

    ``closed_form=True`` selects :func:`pipeline_closed_jax` (totals
    equal to the scan up to rounding; the device sweep fast path).
    """
    with jax.enable_x64(True):
        if isinstance(machines_or_arrays, MachineArrays):
            mp = machines_or_arrays
            if g_max is None:
                g_max = int(np.max(np.asarray(mp.group)))
        else:
            ms = tuple(machines_or_arrays)
            mp = machine_arrays(ms)
            g_max = max(m.group for m in ms)
        m, n, k, b = scenario_arrays(scenarios)
        return _grid_jit(
            m, n, k, b, mp,
            g_max=g_max, schedules=tuple(schedules),
            dma=dma, dma_into_place=dma_into_place,
            closed_form=closed_form,
        )


def evaluate_grid(
    scenarios,
    machines,
    *,
    dma: bool = True,
    dma_into_place: bool = False,
    schedules: tuple[Schedule, ...] = GRID_SCHEDULES,
) -> GridResult:
    """Drop-in jitted replacement for ``repro.core.batch.evaluate_grid``.

    Same :class:`~repro.core.batch.GridResult` out — arrays come back
    from the accelerator and are reshaped to the NumPy engine's
    ``(L, S, M)`` layout, so everything downstream (``GridExploration``,
    benchmarks, heuristic calibration) works unchanged.
    """
    sb = _as_batch(scenarios)
    machines = tuple(machines)
    out = evaluate_grid_raw(
        sb, machines, dma=dma, dma_into_place=dma_into_place,
        schedules=schedules,
    )
    return GridResult.from_machine_major(
        out, schedules=schedules, scenarios=sb, machines=machines, dma=dma
    )


# ---------------------------------------------------------------------------
# Differentiable heuristic: soft decision tree over TAU.
# ---------------------------------------------------------------------------

# Index order of the soft pick, matching GRID_SCHEDULES.
_L_SERIAL = GRID_SCHEDULES.index(Schedule.SERIAL)
_L_UF2 = GRID_SCHEDULES.index(Schedule.UNIFORM_FUSED_2D)
_L_UF1 = GRID_SCHEDULES.index(Schedule.UNIFORM_FUSED_1D)
_L_HF1 = GRID_SCHEDULES.index(Schedule.HETERO_FUSED_1D)
_L_HU1 = GRID_SCHEDULES.index(Schedule.HETERO_UNFUSED_1D)


def soft_pick_weights(
    log_tau, m, k, flops, peak_flops, *, temp=0.15, hard_serial=None
):
    """(S, L) schedule weights: the Fig.-12a tree with sigmoid-relaxed
    TAU comparisons.

    Only the two threshold comparisons involve TAU, so only they are
    softened; the serial escapes (tiny-operator guard + learned serial
    gate, passed in as ``hard_serial``) and the M-vs-K branch stay hard.
    As ``temp -> 0`` this converges to ``select_schedule``'s picks.
    """
    metric = flops  # OTB x MT == FLOPs, like the scalar tree
    log_metric = jnp.log(metric)
    log_t = log_tau + jnp.log(peak_flops)
    # P(metric < T) and P(metric >= 5T), relaxed in log space.
    p_low = jax.nn.sigmoid((log_t - log_metric) / temp)
    p_high = jax.nn.sigmoid((log_metric - (log_t + jnp.log(5.0))) / temp)
    w_uf1 = p_low
    w_hu1 = (1.0 - p_low) * p_high
    w_hf1 = (1.0 - p_low) * (1.0 - p_high)

    S = m.shape[0]
    w = jnp.zeros((S, len(GRID_SCHEDULES)), dtype=log_metric.dtype)
    w = w.at[:, _L_UF1].set(w_uf1)
    w = w.at[:, _L_HU1].set(w_hu1)
    w = w.at[:, _L_HF1].set(w_hf1)
    # Hard branches: 2D when M < K, then the serial escapes (which take
    # precedence over 2D, matching the scalar tree's branch order).
    is_2d = (m < k)[:, None]
    one_hot_2d = jnp.zeros_like(w).at[:, _L_UF2].set(1.0)
    w = jnp.where(is_2d, one_hot_2d, w)
    is_serial = (flops < MIN_DECOMPOSE_FLOPS)[:, None]
    if hard_serial is not None:
        is_serial = is_serial | hard_serial[:, None]
    one_hot_ser = jnp.zeros_like(w).at[:, _L_SERIAL].set(1.0)
    w = jnp.where(is_serial, one_hot_ser, w)
    return w


def expected_heuristic_time(
    tau, scenarios, machine: MachineSpec, *, temp: float = 0.15,
    _precomputed=None,
):
    """Differentiable mean (soft-)heuristic-picked time, normalized by the
    per-scenario optimum.  ``d(this)/d(tau)`` is finite and nonzero —
    the gradient signal :func:`calibrate_tau` descends.
    """
    with jax.enable_x64(True):
        if _precomputed is None:
            _precomputed = _tau_loss_inputs(scenarios, machine)
        m, k, flops, t_norm, peak, hard = _precomputed
        log_tau = jnp.log(jnp.asarray(tau, dtype=_F))
        return _tau_loss(log_tau, m, k, flops, t_norm, peak, hard, temp)


def _tau_loss_inputs(scenarios, machine: MachineSpec):
    """Host-side precompute: normalized valid totals for one machine."""
    from repro.core.heuristics import (
        machine_serial_gate,
        serial_gate_score_batch,
    )

    sb = _as_batch(scenarios)
    out = evaluate_grid_raw(sb, (machine,))
    total = out[0][0]  # (L, S)
    valid = out[5][0]
    gate_scores = serial_gate_score_batch(
        sb.m, sb.n, sb.k, sb.dtype_bytes, machine
    )
    with jax.enable_x64(True):
        m, n, k, b = scenario_arrays(sb)
        flops = 2.0 * (m * n).astype(_F) * k
        best = jnp.min(jnp.where(valid, total, jnp.inf), axis=0)
        # Invalid picks (indivisible decompositions) fall back to serial in
        # the runtime, so charge them the serial time rather than inf/NaN.
        serial = total[_L_SERIAL]
        t_norm = jnp.where(valid, total, serial[None, :]) / best[None, :]
        t_norm = t_norm.T  # (S, L)
        peak = jnp.asarray(machine.peak_flops, dtype=_F)
        hard_serial = jnp.asarray(
            gate_scores > machine_serial_gate(machine), dtype=bool
        )
    return m, k, flops, t_norm, peak, hard_serial


@functools.partial(jax.jit, static_argnames=("temp",))
def _tau_loss(log_tau, m, k, flops, t_norm, peak, hard_serial, temp):
    w = soft_pick_weights(
        log_tau, m, k, flops, peak, temp=temp, hard_serial=hard_serial
    )
    return jnp.mean(jnp.sum(w * t_norm, axis=1))


def calibrate_tau_reference(
    machine: MachineSpec,
    scenarios,
    *,
    temp: float = 0.15,
    lo: float = 1e-4,
    hi: float = 10.0,
    iters: int = 60,
) -> float:
    """Scan + bisection reference for the smooth TAU objective.

    A dense log-spaced scan brackets the global minimum, then bisection
    on the (finite-difference) slope polishes it — the discrete analogue
    the gradient calibration must reproduce.
    """
    pre = _tau_loss_inputs(scenarios, machine)
    m, k, flops, t_norm, peak, hard = pre

    with jax.enable_x64(True):
        taus = np.geomspace(lo, hi, 512)
        losses = np.array([
            float(_tau_loss(jnp.log(jnp.asarray(t, dtype=_F)),
                            m, k, flops, t_norm, peak, hard, temp))
            for t in taus
        ])
        i = int(np.argmin(losses))
        llo = math.log(taus[max(i - 1, 0)])
        lhi = math.log(taus[min(i + 1, len(taus) - 1)])
        eps = 1e-4

        def slope(lt: float) -> float:
            f = lambda x: float(_tau_loss(
                jnp.asarray(x, dtype=_F), m, k, flops, t_norm, peak,
                hard, temp,
            ))
            return (f(lt + eps) - f(lt - eps)) / (2 * eps)

        for _ in range(iters):
            mid = 0.5 * (llo + lhi)
            if slope(mid) < 0.0:
                llo = mid
            else:
                lhi = mid
        return math.exp(0.5 * (llo + lhi))


def calibrate_tau(
    machine: MachineSpec,
    scenarios,
    *,
    steps: int = 120,
    lr: float = 0.08,
    temp: float = 0.15,
    inits=(0.002, 0.02, 0.2, 1.0),
) -> float:
    """Gradient TAU calibration: a few Adam steps on the soft tree loss.

    Replaces the discrete candidate search in
    ``repro.core.heuristics.calibrate_tau`` with first-order descent on
    :func:`expected_heuristic_time` — multi-start (the 1-D landscape can
    have shoulders), best final loss wins.  The result lands on the
    bisection reference (:func:`calibrate_tau_reference`) to well within
    5% on MI300X/Table-I.
    """
    pre = _tau_loss_inputs(scenarios, machine)
    m, k, flops, t_norm, peak, hard = pre

    with jax.enable_x64(True):
        grad_fn = jax.jit(
            jax.value_and_grad(
                lambda lt: _tau_loss(
                    lt, m, k, flops, t_norm, peak, hard, temp
                )
            )
        )

        def adam(log_tau0: float) -> tuple[float, float]:
            lt = jnp.asarray(log_tau0, dtype=_F)
            mu = jnp.zeros((), dtype=_F)
            nu = jnp.zeros((), dtype=_F)
            b1, b2, eps = 0.9, 0.999, 1e-8
            loss = jnp.inf
            for t in range(1, steps + 1):
                loss, g = grad_fn(lt)
                mu = b1 * mu + (1 - b1) * g
                nu = b2 * nu + (1 - b2) * g * g
                mhat = mu / (1 - b1**t)
                nhat = nu / (1 - b2**t)
                lt = lt - lr * mhat / (jnp.sqrt(nhat) + eps)
            loss, _ = grad_fn(lt)
            return float(lt), float(loss)

        results = [adam(math.log(t0)) for t0 in inits]
        best_lt, _ = min(results, key=lambda r: r[1])
        return math.exp(best_lt)


def shortlist(
    gemm,
    machine: MachineSpec,
    *,
    top: int = 3,
    dma: bool = True,
    backend: str = "jax",
    profile=None,
) -> list[tuple[Schedule, float]]:
    """Top-``top`` valid schedules for one GEMM, fastest first.

    ``backend`` names any engine in the :mod:`repro.core.engine`
    registry (``"jax"`` consults the jitted engine; ``"numpy"`` the
    reference engine — useful where no accelerator/XLA is wanted on the
    hot path).  Model times accompany each schedule so callers can
    decide whether measuring is worth it (close calls) or not.
    ``profile`` ranks the schedules under a ragged step profile instead
    of the uniform split (skew-aware tuning).

    This is a thin alias of :func:`repro.core.engine.shortlist`, kept
    for backward compatibility.
    """
    from repro.core.engine import shortlist as _shortlist

    return _shortlist(
        gemm, machine, top=top, dma=dma, backend=backend, profile=profile
    )


__all__ = [
    "MachineArrays",
    "machine_arrays",
    "scenario_arrays",
    "evaluate_grid",
    "evaluate_grid_raw",
    "evaluate_ragged_grid",
    "evaluate_ragged_grid_raw",
    "ragged_step_times_jax",
    "gemm_exec_jax",
    "comm_time_jax",
    "ag_serial_time_jax",
    "p2p_step_time_jax",
    "a2a_chunk_step_time_jax",
    "hbm_move_time_jax",
    "gemm_cil_jax",
    "comm_cil_jax",
    "pipeline_jax",
    "soft_pick_weights",
    "expected_heuristic_time",
    "calibrate_tau",
    "calibrate_tau_reference",
    "shortlist",
]
