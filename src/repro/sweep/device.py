"""Accelerator-resident mixed-precision sweeps (the ``"mixed"`` engine).

Three pieces that together keep a 1e8-lane sweep on the device:

  * **On-device synthesis** — a counter-based splitmix64 generator whose
    numpy twin runs the *identical* arithmetic, so a shard materializes
    from ``(seed, lane_range)`` directly in device memory and
    ``host_batch(...) == device_batch(...)`` exactly for integer fields
    (float fields agree to libm ulps).  Unlike the legacy
    ``sweep/synth.py`` recipes (stateful ``np.random.Generator``
    streams, which jax cannot reproduce), every draw is a pure function
    of ``(seed, field, lane)`` — shard-composable by construction: lane
    ``i`` draws the same scenario no matter how the sweep is sharded.
    This deviates from the issue's "port to ``jax.random``" letter
    deliberately: ``jax.random`` streams cannot be twinned on the host
    for parity tests, and counter addressing is what makes shard
    boundaries free.
  * **Mixed-precision evaluation** — :func:`evaluate_mixed_grid` /
    :func:`dispatch_mixed_grid` pack the machine leaves at
    bf16/f32/f64 (``repro.autotune.jaxgrid.machine_arrays(dtype=...)``)
    and reuse the jitted kernels unchanged; the pipeline scan still
    accumulates in float64 (see ``jaxgrid.pipeline_jax``).  The
    two-phase ``dispatch`` form returns a ``finalize()`` thunk so the
    double-buffered shard loop can keep shard ``k+1`` in flight while
    shard ``k`` materializes — the paper's own overlap discipline
    applied to the sweep itself.
  * **Fused statistics reduction** — :func:`sweep_device_stats` runs
    synthesis, grid evaluation *and* the :class:`~repro.learn.stats.
    GateStats` integer-histogram reduction inside one jit, so only the
    (feature-bin, score-bin) histogram and a few summary scalars ever
    leave the accelerator; no ``(L, S, M)`` ``GridResult`` is assembled
    off-device.  The heuristic twins (gate terms, base picks, feature
    matrix) are computed in float64 on-device regardless of the
    evaluation dtype, mirroring ``repro.learn.stats.GateStats.
    update_from_grid`` operation for operation.

Dirichlet note: ragged profiles use Marsaglia–Tsang gamma sampling
(boosted for concentration < 1) with four fixed, vectorized
accept-rounds; the ~1e-5 of lanes still unresolved after four rounds
fall back deterministically to the distribution mode.  The profiles are
distribution-equivalent to ``synth.synthetic_ragged_batch`` but not
stream-identical to it — parity is defined against the numpy twin
(:func:`host_ragged_batch`), which runs the same arithmetic.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np

from repro.core.batch import RaggedBatch, ScenarioBatch
from repro.core.engine import (
    GRID_SCHEDULES,
    SCHEDULE_INDEX,
    GridResult,
    as_scenario_sequence,
    is_ragged,
)
from repro.core.heuristics import (
    _GATE_COMM_CIL,
    MIN_DECOMPOSE_FLOPS,
    machine_threshold,
)
from repro.core.schedule_types import Schedule
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.sweep.plan import plan_shards, shards_for_host
from repro.sweep.runner import ShardSummary, SweepResult
from repro.sweep.synth import _M_QUANTUM

# ---------------------------------------------------------------------------
# Counter-based generator (splitmix64): identical on numpy and jax.
# ---------------------------------------------------------------------------

_MASK64 = (1 << 64) - 1
_U_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_U_MIX2 = np.uint64(0x94D049BB133111EB)
_U_GOLD = np.uint64(0x9E3779B97F4A7C15)

# Field addresses (the per-(seed, field) key spaces never collide).
_FIELD_M, _FIELD_N, _FIELD_K, _FIELD_B, _FIELD_SHORT, _FIELD_TAIL = range(6)
_FIELD_GAMMA0 = 16  # gamma draws for ragged step s start at 16 + 16*s
_GAMMA_STRIDE = 16
_GAMMA_ROUNDS = 4  # fixed vectorized accept-rounds (3 draws each)
_GAMMA_BOOST = 12  # 13th draw of a step: the alpha<1 boost uniform


def _mix64_int(x: int) -> int:
    """Scalar splitmix64 finalizer on python ints (key derivation)."""
    z = x & _MASK64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK64
    return z ^ (z >> 31)


def _field_key(seed: int, field: int) -> int:
    """Per-(seed, field) stream key — a plain python int, so it is a
    compile-time constant inside the jitted program."""
    return _mix64_int(
        (_mix64_int(seed & _MASK64) + field * 0x9E3779B97F4A7C15) & _MASK64
    )


def _mix64(xp, z):
    """Vector splitmix64 finalizer; ``xp`` is numpy or jax.numpy.

    numpy uint64 arithmetic wraps silently; jax needs the x64 scope the
    device entry points always hold.
    """
    z = (z ^ (z >> np.uint64(30))) * _U_MIX1
    z = (z ^ (z >> np.uint64(27))) * _U_MIX2
    return z ^ (z >> np.uint64(31))


def _u01(xp, key: int, lane):
    """Uniform draw in (0, 1] (log-safe), exact function of (key, lane).

    The top 53 bits map to ``(k + 1) * 2**-53`` — every step (integer
    ops, uint64->f64 of values <= 2**53, power-of-two scaling) is exact,
    so numpy and jax produce bitwise-identical uniforms.
    """
    bits = _mix64(xp, np.uint64(key) + lane * _U_GOLD)
    return ((bits >> np.uint64(11)) + np.uint64(1)).astype(
        xp.float64
    ) * (2.0 ** -53)


def _lanes(xp, n: int, start):
    """uint64 lane ids ``start + [0, n)``; ``start`` may be traced."""
    if xp is np:
        start = np.uint64(int(start))
    return start + xp.arange(n, dtype=xp.uint64)


# ---------------------------------------------------------------------------
# Synthesis twins (xp-generic; xp=np is the host twin, xp=jnp the device).
# ---------------------------------------------------------------------------


def _int_field(xp, key: int, lane, quantum: int, lo: float, hi: float):
    """``quantum * int(exp(U(log lo, log hi)))`` — the synth.py recipe
    (truncate-then-multiply, matching ``synthetic_batch``)."""
    u = _u01(xp, key, lane)
    v = xp.exp(u * (math.log(hi) - math.log(lo)) + math.log(lo))
    return quantum * v.astype(xp.int64)


def _choice_field(xp, key: int, lane, choices):
    u = _u01(xp, key, lane)
    i = xp.minimum(
        xp.floor(u * len(choices)).astype(xp.int64), len(choices) - 1
    )
    return xp.asarray(choices, dtype=xp.int64)[i]


def _synth_uniform(xp, lane, seed: int, dtype_bytes):
    """(m, n, k, b) int64 arrays; same ranges as ``synthetic_batch``."""
    m = _int_field(xp, _field_key(seed, _FIELD_M), lane, _M_QUANTUM, 1, 2048)
    n = _int_field(xp, _field_key(seed, _FIELD_N), lane, 128, 8, 512)
    k = _int_field(xp, _field_key(seed, _FIELD_K), lane, 128, 8, 512)
    b = _choice_field(xp, _field_key(seed, _FIELD_B), lane, tuple(dtype_bytes))
    return m, n, k, b


def _gamma_boosted(xp, seed: int, lane, step: int, alpha: float):
    """Gamma(alpha) draws via Marsaglia–Tsang at ``alpha + 1`` plus the
    ``u**(1/alpha)`` boost (alpha < 1 support), vectorized.

    Four fixed accept-rounds resolve all but ~1e-5 of lanes (the M–T
    acceptance rate at the boosted shape is >95%); stragglers fall back
    deterministically to ``d`` (the distribution mode) so the result is
    a pure function of (seed, step, lane) with no data-dependent loop.
    """
    d = (alpha + 1.0) - 1.0 / 3.0
    c = 1.0 / math.sqrt(9.0 * d)
    base = _FIELD_GAMMA0 + step * _GAMMA_STRIDE
    g = xp.full(lane.shape, -1.0, dtype=xp.float64)
    for j in range(_GAMMA_ROUNDS):
        u1 = _u01(xp, _field_key(seed, base + 3 * j), lane)
        u2 = _u01(xp, _field_key(seed, base + 3 * j + 1), lane)
        ua = _u01(xp, _field_key(seed, base + 3 * j + 2), lane)
        # Box–Muller normal from two (0, 1] uniforms.
        x = xp.sqrt(-2.0 * xp.log(u1)) * xp.cos((2.0 * math.pi) * u2)
        v = (1.0 + c * x) ** 3
        v_safe = xp.where(v > 0.0, v, 1.0)
        ok = (v > 0.0) & (
            xp.log(ua) < 0.5 * x * x + d - d * v_safe + d * xp.log(v_safe)
        )
        g = xp.where((g < 0.0) & ok, d * v_safe, g)
    g = xp.where(g < 0.0, d, g)
    boost = _u01(xp, _field_key(seed, base + _GAMMA_BOOST), lane)
    return g * boost ** (1.0 / alpha)


def _synth_frac(xp, lane, seed: int, steps: int, concentration: float):
    """(S, steps) float64 Dirichlet profiles with masked short tails.

    Mirrors ``synthetic_ragged_batch``'s post-processing: ~25% of rows
    are truncated to a random tail in [1, steps-1], then rows
    renormalize to sum to 1 exactly.
    """
    gs = xp.stack(
        [
            _gamma_boosted(xp, seed, lane, s, concentration)
            for s in range(steps)
        ],
        axis=1,
    )
    if steps > 1:
        short = _u01(xp, _field_key(seed, _FIELD_SHORT), lane) < 0.25
        u_tail = _u01(xp, _field_key(seed, _FIELD_TAIL), lane)
        tail = xp.minimum(
            (1.0 + xp.floor(u_tail * (steps - 1))).astype(xp.int64),
            steps - 1,
        )
        cols = xp.arange(steps, dtype=xp.int64)[None, :]
        gs = xp.where(short[:, None] & (cols >= tail[:, None]), 0.0, gs)
    return gs / gs.sum(axis=1, keepdims=True)


def host_batch(
    n: int, *, seed: int = 0, start: int = 0, dtype_bytes=(2, 1)
) -> ScenarioBatch:
    """Numpy twin of :func:`device_batch` — bitwise-identical integers.

    ``start`` is the global lane offset: ``host_batch(k, start=s)`` is
    rows ``[s, s+k)`` of ``host_batch(s+k)``, which is what lets every
    shard regenerate exactly its slice.
    """
    lane = _lanes(np, n, start)
    m, nn, kk, b = _synth_uniform(np, lane, seed, dtype_bytes)
    return ScenarioBatch(m=m, n=nn, k=kk, dtype_bytes=b)


def host_ragged_batch(
    n: int,
    *,
    seed: int = 0,
    start: int = 0,
    steps: int = 8,
    concentration: float = 0.7,
    dtype_bytes=(2, 1),
) -> RaggedBatch:
    """Numpy twin of :func:`device_ragged_batch`."""
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    lane = _lanes(np, n, start)
    m, nn, kk, b = _synth_uniform(np, lane, seed, dtype_bytes)
    frac = _synth_frac(np, lane, seed, steps, concentration)
    return RaggedBatch(m=m, n=nn, k=kk, dtype_bytes=b, frac=frac)


def device_batch(
    n: int, *, seed: int = 0, start: int = 0, dtype_bytes=(2, 1)
) -> ScenarioBatch:
    """On-device synthesis, materialized back as a ScenarioBatch.

    The materialized form exists for parity tests and engine reuse; the
    fused sweep (:func:`sweep_device_stats`) never leaves the device.
    """
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        lane = _lanes(jnp, n, np.uint64(start))
        m, nn, kk, b = _synth_uniform(jnp, lane, seed, dtype_bytes)
        return ScenarioBatch(
            m=np.asarray(m), n=np.asarray(nn), k=np.asarray(kk),
            dtype_bytes=np.asarray(b),
        )


def device_ragged_batch(
    n: int,
    *,
    seed: int = 0,
    start: int = 0,
    steps: int = 8,
    concentration: float = 0.7,
    dtype_bytes=(2, 1),
) -> RaggedBatch:
    """On-device ragged synthesis, materialized as a RaggedBatch."""
    import jax
    import jax.numpy as jnp

    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    with jax.enable_x64(True):
        lane = _lanes(jnp, n, np.uint64(start))
        m, nn, kk, b = _synth_uniform(jnp, lane, seed, dtype_bytes)
        frac = _synth_frac(jnp, lane, seed, steps, concentration)
        return RaggedBatch(
            m=np.asarray(m), n=np.asarray(nn), k=np.asarray(kk),
            dtype_bytes=np.asarray(b), frac=np.asarray(frac),
        )


# ---------------------------------------------------------------------------
# Mixed-precision grid evaluation (the "mixed" engine's backend).
# ---------------------------------------------------------------------------

_DTYPES = ("float64", "float32", "bfloat16")


def _coerce(scenarios):
    from repro.core import batch as _batch

    scenarios = as_scenario_sequence(scenarios)
    if is_ragged(scenarios):
        return _batch._as_ragged_batch(scenarios)
    return _batch._as_batch(scenarios)


def dispatch_mixed_grid(
    scenarios,
    machines,
    *,
    dtype: str = "float32",
    dma: bool = True,
    dma_into_place: bool = False,
    schedules=GRID_SCHEDULES,
):
    """Asynchronously dispatch a mixed-precision grid evaluation.

    Returns a zero-argument ``finalize()`` that materializes the
    :class:`GridResult` (blocking on device completion).  jax dispatch
    is asynchronous, so the device starts computing the moment this
    returns — the double-buffered shard loop dispatches shard ``k+1``
    before finalizing shard ``k``.
    """

    import jax
    from repro.autotune import jaxgrid

    if dtype not in _DTYPES:
        raise ValueError(f"dtype must be one of {_DTYPES}, got {dtype!r}")
    machines = tuple(machines)
    schedules = tuple(schedules)
    sb = _coerce(scenarios)
    with _trace.span(
        "sweepdevice/dispatch", "sweepdevice",
        dtype=dtype, n_scenarios=len(sb), n_machines=len(machines),
    ):
        with jax.enable_x64(True):
            # Machine arrays MUST pack inside the x64 scope: outside it
            # the int64 leaves silently truncate to int32.
            mp = jaxgrid.machine_arrays(
                machines, dtype=None if dtype == "float64" else dtype
            )
            g_max = max(m.group for m in machines)
            if isinstance(sb, RaggedBatch):
                out = jaxgrid.evaluate_ragged_grid_raw(
                    sb, mp, dma=dma, dma_into_place=dma_into_place,
                    schedules=schedules, g_max=g_max,
                )
            else:
                out = jaxgrid.evaluate_grid_raw(
                    sb, mp, dma=dma, dma_into_place=dma_into_place,
                    schedules=schedules, g_max=g_max,
                )

    def finalize() -> GridResult:
        # The np.asarray conversions inside from_machine_major block on
        # the async device computation — this span is the "compute"
        # half of the two-phase overlap.
        with _trace.span(
            "sweepdevice/finalize", "sweepdevice",
            dtype=dtype, n_scenarios=len(sb),
        ):
            return GridResult.from_machine_major(
                out, schedules=schedules, scenarios=sb, machines=machines,
                dma=dma,
            )

    return finalize


def evaluate_mixed_grid(
    scenarios,
    machines,
    *,
    dtype: str = "float32",
    dma: bool = True,
    dma_into_place: bool = False,
    schedules=GRID_SCHEDULES,
) -> GridResult:
    """Synchronous form of :func:`dispatch_mixed_grid`."""
    return dispatch_mixed_grid(
        scenarios, machines, dtype=dtype, dma=dma,
        dma_into_place=dma_into_place, schedules=schedules,
    )()


# ---------------------------------------------------------------------------
# Fused synthesis + evaluation + GateStats reduction (one jit).
# ---------------------------------------------------------------------------


def _quantize_regret_jnp(t, tb):
    """jnp twin of ``repro.learn.stats._quantize_regret`` (rint is
    round-half-even on both sides)."""
    import jax.numpy as jnp

    from repro.learn.stats import REGRET_CAP, REGRET_SCALE

    regret = t / tb - 1.0
    regret = jnp.nan_to_num(
        regret, nan=REGRET_CAP, posinf=REGRET_CAP, neginf=0.0
    )
    regret = jnp.clip(regret, 0.0, REGRET_CAP)
    return jnp.rint(regret * REGRET_SCALE).astype(jnp.int64)


def _stats_one_machine(m, n, k, b, imb, act, row, thr, t, tb):
    """One machine's GateStats contribution, all float64, on device.

    Twins ``GateStats.update_from_grid``'s per-machine body operation
    for operation (terms -> score -> base picks -> features -> binned
    integer scatter): casts, op order and bin conventions match the
    numpy source exactly, so the integer histogram agrees with the host
    reduction up to float ulps landing on bin edges (measure-zero in
    practice; the parity test bounds the stray mass).

    ``row`` is a float64 MachineArrays row; ``t`` is the machine's
    nan_to_num'd (L, S) total; ``tb`` its (S,) best total; ``act`` is
    None for uniform batches (the ``group`` sentinel).
    """
    import jax.numpy as jnp

    from repro.autotune import jaxgrid
    from repro.learn.stats import (
        FEATURE_EDGES,
        SCORE_EDGES,
        _hist_shape,
    )
    from repro.learn.features import GATE_FEATURES

    f64 = jnp.float64
    mf, nf, kf, bf = (a.astype(f64) for a in (m, n, k, b))
    g = row.group
    gf = g.astype(f64)

    # -- serial_gate_terms_batch twin (floats first, like the source) --
    dev_n = jnp.where(nf % gf == 0.0, nf / gf, nf)
    mk_bytes = mf * kf * bf
    ag_bw = jnp.where(
        row.is_mesh,
        row.link_bw * (g - 1).astype(f64),
        row.link_bw * row.a2a_links.astype(f64),
    )
    t_comm = mk_bytes / ag_bw
    t_gemm = 2.0 * mf * dev_n * kf / row.peak_flops
    r = t_comm / t_gemm
    t_serial_ag = jaxgrid.ag_serial_time_jax(mk_bytes, row)
    t_chunked_ag = gf * jaxgrid.a2a_chunk_step_time_jax(
        mk_bytes / (gf * gf), row
    )
    inflate = t_chunked_ag / t_serial_ag
    score = r * (inflate * _GATE_COMM_CIL - 1.0)

    # -- select_schedule_batch twin (serial_gate=inf -> flops guard) ---
    flops_i = 2.0 * m * n * k  # int chain, matching the numpy source
    bytes_i = (m * k + k * n + m * n).astype(f64) * b
    metric = (flops_i / bytes_i) * bytes_i
    base = jnp.select(
        [
            flops_i < MIN_DECOMPOSE_FLOPS,
            m < k,
            metric < thr,
            metric >= 5.0 * thr,
        ],
        [
            SCHEDULE_INDEX[Schedule.SERIAL],
            SCHEDULE_INDEX[Schedule.UNIFORM_FUSED_2D],
            SCHEDULE_INDEX[Schedule.UNIFORM_FUSED_1D],
            SCHEDULE_INDEX[Schedule.HETERO_UNFUSED_1D],
        ],
        SCHEDULE_INDEX[Schedule.HETERO_FUSED_1D],
    ).astype(jnp.int32)

    # -- feature_matrix twin (floats-first sums, unlike the picks) -----
    act_col = jnp.ones_like(imb) * gf if act is None else act
    flops_f = 2.0 * mf * nf * kf
    bytes_f = (mf * kf + kf * nf + mf * nf) * bf
    otb = flops_f / bytes_f
    m_over_k = mf / kf
    log_flops = jnp.log10(jnp.maximum(flops_f, 1.0))
    cil = jaxgrid.comm_cil_jax(mf / gf, dev_n, kf, bf, row, degree=4)
    feats = jnp.stack(
        [
            imb, act_col, otb, r, inflate, cil, log_flops, m_over_k,
            jnp.ones_like(imb) * gf,
            jnp.ones_like(imb) * (row.peak_flops / row.hbm_bw),
        ],
        axis=1,
    )

    # -- binning + integer scatter (GATE_FEATURES order, then score) ---
    gate_cols = {"imbalance": imb, "active_steps": act_col, "otb": otb,
                 "r": r}
    idx = jnp.zeros(imb.shape, dtype=jnp.int64)
    for fname in GATE_FEATURES:
        edges = jnp.asarray(FEATURE_EDGES[fname], dtype=f64)
        idx = idx * (len(FEATURE_EDGES[fname]) + 1) + jnp.searchsorted(
            edges, gate_cols[fname], side="right"
        )
    idx = idx * (len(SCORE_EDGES) + 1) + jnp.searchsorted(
        jnp.asarray(SCORE_EDGES, dtype=f64), score, side="right"
    )

    serial_l = SCHEDULE_INDEX[Schedule.SERIAL]
    t_serial = t[serial_l, :]
    # base only ever holds the five pick indices; a select chain over
    # contiguous rows avoids a strided take_along_axis gather.
    picks = sorted({
        SCHEDULE_INDEX[s] for s in (
            Schedule.SERIAL, Schedule.UNIFORM_FUSED_2D,
            Schedule.UNIFORM_FUSED_1D, Schedule.HETERO_UNFUSED_1D,
            Schedule.HETERO_FUSED_1D,
        )
    })
    t_pick = jnp.select(
        [base == j for j in picks], [t[j, :] for j in picks], jnp.inf
    )
    w5_serial = (t_serial <= 1.05 * tb).astype(jnp.int64)
    w5_base = (t_pick <= 1.05 * tb).astype(jnp.int64)
    reg_serial = _quantize_regret_jnp(t_serial, tb)
    reg_base = _quantize_regret_jnp(t_pick, tb)

    shape = _hist_shape()
    flat = int(np.prod(shape[:-1]))
    # One fused scatter of the (S, 5) stat payload beats five scatter
    # passes over the 874k-cell histogram by ~4x on CPU.
    payload = jnp.stack(
        [
            jnp.ones_like(w5_serial), w5_serial, w5_base,
            reg_serial, reg_base,
        ],
        axis=1,
    )
    h = jnp.zeros((flat, shape[-1]), dtype=jnp.int64)
    h = h.at[idx].add(payload)

    finite = jnp.isfinite(feats)
    mom = jnp.stack(
        [
            finite.sum(axis=0).astype(f64),
            jnp.where(finite, feats, 0.0).sum(axis=0),
            jnp.where(finite, feats ** 2, 0.0).sum(axis=0),
        ],
        axis=1,
    )
    return h, mom


@functools.lru_cache(maxsize=None)
def _shard_fn():
    """Build (once) the jitted fused shard program.

    Deferred so importing this module never imports jax; the jit caches
    per static-argument combination as usual.
    """
    import jax
    import jax.numpy as jnp

    from repro.autotune import jaxgrid

    @functools.partial(
        jax.jit,
        static_argnames=(
            "n", "seed", "steps", "concentration", "dtype_bytes",
            "g_max", "dma", "dma_into_place", "collect", "per_machine",
        ),
    )
    def shard_fn(
        start, mp_dt, mp64, thresholds, *,
        n, seed, steps, concentration, dtype_bytes,
        g_max, dma, dma_into_place, collect, per_machine,
    ):
        lane = start + jnp.arange(n, dtype=jnp.uint64)
        m, nn, kk, b = _synth_uniform(jnp, lane, seed, dtype_bytes)
        frac64 = (
            None if steps is None
            else _synth_frac(jnp, lane, seed, steps, concentration)
        )
        dt = mp_dt.peak_flops.dtype
        if frac64 is None:
            # closed_form=True: uniform schedules use the exact
            # closed-form pipeline (equal to the scan up to rounding),
            # ~2x fewer elementwise ops — the sweep fast path.
            outs = jax.vmap(
                lambda one: jaxgrid._eval_one_machine_jax(
                    m, nn, kk, b, one, g_max, GRID_SCHEDULES,
                    dma, dma_into_place, True,
                )
            )(mp_dt)
        else:
            frac_dt = frac64.astype(dt)
            outs = jax.vmap(
                lambda one: jaxgrid._eval_one_machine_ragged_jax(
                    m, nn, kk, b, frac_dt, one, g_max, GRID_SCHEDULES,
                    dma, dma_into_place,
                )
            )(mp_dt)
        total, _c, _w, _e, _st, valid, sc, sg = outs
        L = len(GRID_SCHEDULES)
        serial_l = SCHEDULE_INDEX[Schedule.SERIAL]
        tv = jnp.where(valid, total, jnp.inf)
        # Min/argmin over the schedule axis as L contiguous (M, S)
        # passes: lanes sit 2 MB apart along axis 1, so the native
        # jnp.argmin(axis=1) gather pattern thrashes the cache.
        tb = tv[:, 0, :]
        best = jnp.zeros(tb.shape, dtype=jnp.int32)
        for j in range(1, L):
            better = tv[:, j, :] < tb
            tb = jnp.where(better, tv[:, j, :], tb)
            best = jnp.where(better, jnp.int32(j), best)
        best_counts = jax.vmap(
            lambda bj: jnp.zeros((L,), dtype=jnp.int64).at[bj].add(1)
        )(best)  # (M, L) — scatter beats an (M, S, L) one-hot sum

        n_prof = jnp.sum(best != serial_l)
        speedup = (sc + sg) / tb
        fin = jnp.isfinite(speedup)
        sp_sum = jnp.sum(jnp.where(fin, speedup, 0.0))
        sp_cnt = jnp.sum(fin)
        if not collect:
            return best_counts, n_prof, sp_sum, sp_cnt

        if frac64 is None:
            imb = jnp.ones((n,), dtype=jnp.float64)
            act = None
        else:
            act = (frac64 > 0.0).sum(axis=1).astype(jnp.float64)
            imb = frac64.max(axis=1) * act
        t = jnp.nan_to_num(total, nan=jnp.inf, posinf=jnp.inf)
        hist, mom = jax.vmap(
            lambda row, thr, t_j, tb_j: _stats_one_machine(
                m, nn, kk, b, imb, act, row, thr, t_j, tb_j
            )
        )(mp64, thresholds, t, tb)
        if not per_machine:
            hist = hist.sum(axis=0)
            mom = mom.sum(axis=0)
        return best_counts, n_prof, sp_sum, sp_cnt, hist, mom

    return shard_fn


def sweep_device_stats(
    n_scenarios: int,
    machines,
    *,
    seed: int = 0,
    dtype: str = "float32",
    num_shards: int | None = None,
    ragged: bool = False,
    steps: int = 8,
    concentration: float = 0.7,
    dtype_bytes=(2, 1),
    dma: bool = True,
    dma_into_place: bool = False,
    host_index: int = 0,
    host_count: int = 1,
    on_shard=None,
    overlap_dispatch: bool = True,
    collect_stats: bool = True,
    per_family: bool = False,
):
    """The fully device-resident sweep: synth + eval + stats in one jit.

    Shards the global lane range ``[0, n_scenarios)`` with the standard
    deterministic plan (so multi-host runs regenerate exactly their
    owned lanes), dispatches each owned shard's fused program, and —
    with ``overlap_dispatch`` (default on; this path has no bit-identity
    contract to preserve) — keeps shard ``k+1`` in flight while shard
    ``k``'s reduced outputs transfer.  Per-shard ``seconds`` therefore
    overlap wall-clock; their sum exceeds elapsed time by design.

    Returns ``(stats, sweep_result)``:

      * ``stats`` — a :class:`~repro.learn.stats.GateStats` (or, with
        ``per_family=True``, a dict mapping machine-family name — the
        ``name.split("/")[0]`` prefix — to its own GateStats; families
        sum to the global statistics exactly).  ``None`` when
        ``collect_stats=False``.
      * ``sweep_result`` — a reduce-mode :class:`SweepResult` whose
        summaries mirror ``sweep_grid``'s (``on_shard`` streams them).

    The GateStats histogram is reduced in the jit from float64 heuristic
    twins, so a gate trained from it matches host-reduced training up to
    bin-edge ulps regardless of the evaluation ``dtype``.
    """
    import jax
    import jax.numpy as jnp

    from repro.autotune import jaxgrid
    from repro.learn.stats import GateStats, _hist_shape

    if dtype not in _DTYPES:
        raise ValueError(f"dtype must be one of {_DTYPES}, got {dtype!r}")
    if ragged and steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    machines = tuple(machines)
    M = len(machines)
    families = [m.name.split("/", 1)[0] for m in machines]
    L = len(GRID_SCHEDULES)
    shard_fn = _shard_fn()
    per_machine = bool(per_family and collect_stats)

    plan = plan_shards(
        n_scenarios, num_shards if num_shards is not None else host_count
    )
    owned = shards_for_host(plan, host_index, host_count)

    summaries: list[ShardSummary] = []
    hist_acc: dict[str, np.ndarray] = {}
    mom_acc: dict[str, np.ndarray] = {}
    pts_acc: dict[str, int] = {}
    bc_acc: dict[str, np.ndarray] = {}
    shape = _hist_shape()
    flat = int(np.prod(shape[:-1]))

    def _bucket(key):
        if key not in hist_acc:
            hist_acc[key] = np.zeros((flat, shape[-1]), dtype=np.int64)
            mom_acc[key] = np.zeros((len(_feature_count()), 3))
            pts_acc[key] = 0
            bc_acc[key] = np.zeros(L, dtype=np.int64)
        return key

    with jax.enable_x64(True):
        mp_dt = jaxgrid.machine_arrays(
            machines, dtype=None if dtype == "float64" else dtype
        )
        mp64 = jaxgrid.machine_arrays(machines)
        thresholds = jnp.asarray(
            [machine_threshold(m) for m in machines], dtype=jnp.float64
        )
        g_max = max(m.group for m in machines)

        reg = _metrics.get_metrics()

        def _dispatch(shard):
            start, stop = plan.bounds[shard]
            t0 = time.perf_counter()
            with _trace.span(
                "sweepdevice/dispatch", "sweepdevice",
                shard=shard, start=start, stop=stop,
                overlap=overlap_dispatch,
            ):
                outs = shard_fn(
                    np.uint64(start), mp_dt, mp64, thresholds,
                    n=stop - start, seed=seed,
                    steps=steps if ragged else None,
                    concentration=concentration,
                    dtype_bytes=tuple(dtype_bytes),
                    g_max=g_max, dma=dma, dma_into_place=dma_into_place,
                    collect=collect_stats, per_machine=per_machine,
                )
            return (shard, start, stop, t0, outs)

        def _complete(entry):
            shard, start, stop, t0, outs = entry
            with _trace.span(
                "sweepdevice/compute", "sweepdevice", shard=shard,
            ):
                host = [np.asarray(o) for o in outs]  # blocks on device
            secs = time.perf_counter() - t0
            S = stop - start
            reg.counter("sweep/shards").inc()
            reg.counter("sweep/scenarios").inc(S)
            reg.histogram("sweep/shard_seconds").observe(secs)
            with _trace.span(
                "sweepdevice/reduce", "sweepdevice",
                shard=shard, n_scenarios=S, seconds=secs,
            ):
                bc_ml, n_prof, sp_sum, sp_cnt = host[:4]
                bc = bc_ml.sum(axis=0)
                counts = {
                    sched.value: int(c)
                    for sched, c in zip(GRID_SCHEDULES, bc) if c
                }
                summ = ShardSummary(
                    shard=shard, start=start, stop=stop, n_scenarios=S,
                    n_points=S * M, seconds=secs,
                    scenarios_per_sec=S / secs if secs > 0 else 0.0,
                    best_counts=counts,
                    frac_overlap_profitable=float(n_prof) / (S * M),
                    mean_best_speedup=(
                        float(sp_sum) / float(sp_cnt) if sp_cnt else 0.0
                    ),
                )
                if collect_stats:
                    hist, mom = host[4], host[5]
                    if per_machine:
                        for j, fam in enumerate(families):
                            key = _bucket(fam)
                            hist_acc[key] += hist[j]
                            mom_acc[key] += mom[j]
                            pts_acc[key] += S
                            bc_acc[key] += bc_ml[j]
                    else:
                        key = _bucket("__all__")
                        hist_acc[key] += hist
                        mom_acc[key] += mom
                        pts_acc[key] += S * M
                        bc_acc[key] += bc
                summaries.append(summ)
                if on_shard is not None:
                    on_shard(summ)

        pending = None
        for shard in owned:
            start, stop = plan.bounds[shard]
            if start == stop:
                if pending is not None:
                    _complete(pending)
                    pending = None
                summ = ShardSummary(
                    shard, start, stop, 0, 0, 0.0, 0.0, {}, 0.0, 0.0
                )
                summaries.append(summ)
                if on_shard is not None:
                    on_shard(summ)
                continue
            entry = _dispatch(shard)
            if pending is not None:
                _complete(pending)
            if overlap_dispatch:
                pending = entry
            else:
                _complete(entry)
        if pending is not None:
            _complete(pending)

    def _as_stats(key) -> GateStats:
        st = GateStats.empty()
        st.hist = st.hist + hist_acc[key].reshape(st.hist.shape)
        st.moments = st.moments + mom_acc[key]
        st.best_counts = {
            sched.value: int(c)
            for sched, c in zip(GRID_SCHEDULES, bc_acc[key]) if c
        }
        st.n_points = pts_acc[key]
        return st

    stats = None
    if collect_stats:
        if per_family:
            stats = {
                fam: _as_stats(_bucket(fam))
                for fam in dict.fromkeys(families)
            }
        else:
            stats = _as_stats("__all__") if hist_acc else GateStats.empty()

    result = SweepResult(
        plan=plan, mode="reduce", host_index=host_index,
        host_count=host_count, owned=owned, summaries=tuple(summaries),
        grid=None,
    )
    return stats, result


def _feature_count():
    from repro.learn.features import FEATURE_NAMES

    return FEATURE_NAMES


def device_merge_stats(stats_list):
    """Device-side multi-host :class:`GateStats` merge.

    The multi-host stat streams (``sweep_host*.jsonl``) merge their
    integer histograms on the accelerator instead of the host: when the
    local device count covers the list, each histogram is laid on its
    own device and a ``psum`` over a ``"hosts"`` axis reduces them —
    the same collective a real multi-host pod would run, exercised here
    on simulated devices; longer lists fall back to a jitted on-device
    sum.  int64 addition is associative and exact, so either path is
    bit-identical to the host-side left fold
    ``functools.reduce(GateStats.merge, stats_list)``.  The float
    moments and the best-count/point tallies are reporting-only and
    tiny; they fold on the host in list order so even their float
    rounding matches the ``merge`` chain.
    """
    import jax
    import jax.numpy as jnp

    from repro.learn.stats import GateStats

    stats_list = list(stats_list)
    if not stats_list:
        return GateStats.empty()
    first = stats_list[0]
    for other in stats_list[1:]:
        if other.schema != first.schema:
            raise ValueError(
                f"cannot merge GateStats schema {other.schema} "
                f"into schema {first.schema}"
            )
        if other.hist.shape != first.hist.shape:
            raise ValueError("GateStats bin layouts differ")
    with jax.enable_x64(True):
        stacked = jnp.asarray(
            np.stack([s.hist for s in stats_list]), dtype=jnp.int64
        )
        if len(stats_list) <= jax.local_device_count():
            merged = jax.pmap(
                lambda h: jax.lax.psum(h, "hosts"), axis_name="hosts"
            )(stacked)[0]
        else:
            merged = jax.jit(lambda h: h.sum(axis=0))(stacked)
        hist = np.asarray(merged)

    moments = first.moments.copy()
    counts = dict(first.best_counts)
    n_points = first.n_points
    for other in stats_list[1:]:
        moments = moments + other.moments
        for key, v in other.best_counts.items():
            counts[key] = counts.get(key, 0) + v
        n_points += other.n_points
    return GateStats(
        hist=hist,
        moments=moments,
        best_counts=counts,
        n_points=n_points,
        schema=first.schema,
    )


__all__ = [
    "host_batch",
    "host_ragged_batch",
    "device_batch",
    "device_ragged_batch",
    "evaluate_mixed_grid",
    "dispatch_mixed_grid",
    "sweep_device_stats",
    "device_merge_stats",
]
