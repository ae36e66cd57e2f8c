"""Accelerator-resident mixed-precision sweep (``repro.sweep.device``).

Contracts locked here:

  * the ``"mixed"`` engine registers with honest capability flags and a
    float64 mode that is **bit-identical** to the jitted jax engine
    (same kernel, same accumulator);
  * float32/bfloat16 evaluation tracks the float64 engine within a
    documented tolerance on the degenerate zoo + Table I + ragged
    profiles, with exactly equal validity masks (masking is integer
    logic, never dtype-dependent);
  * on-device counter-based synthesis is bitwise-identical to its host
    numpy twin (integers exact, Dirichlet fractions to f64 rounding)
    and shard-composable (``start`` slices the global lane stream);
  * the fused synth+eval+stats program reproduces host-side
    ``sweep_stats`` bit-for-bit at float64, and at float32 is exactly
    the statistics of its own materialized grid (the "same-dtype twin"
    — the histogram's feature/score axes are f64 on both sides, so
    count columns never move with the evaluation dtype);
  * a gate trained from mixed-precision device statistics reproduces
    the float64-trained gate: identical tree structure and split edges,
    leaf thresholds within one score-bin quantum;
  * double-buffered dispatch (runner ``overlap_dispatch`` and the fused
    sweep's default) changes throughput, never results;
  * the closed-form uniform pipeline used by the fused path matches the
    scan to float64 rounding and never flips an argmin at grid scale;
  * ``_floor_div`` (vectorizable f64 floor-division) is exact over the
    synthesizable shape range, including the negated-ceil pattern.
"""

import numpy as np
import pytest

from repro.core import TABLE_I, engine_names, get_engine
from repro.core.machine import MI300X, TPU_V5E
from repro.core.batch import ScenarioBatch
from repro.core.workload import GemmShape, machine_grid

from grid_asserts import assert_grid_identical

pytestmark = pytest.mark.autotune

MACHINES = machine_grid(groups=(8,))

# The engine-suite degenerate zoo (indivisible / zero-row shapes) as a
# batch, plus Table I.
ZOO = [
    GemmShape(8192, 57344, 8192),
    GemmShape(1001, 4096, 4096),  # m not divisible by any group
    GemmShape(32, 4096, 4096),  # hetero chunk rows would be 0
    GemmShape(8192, 8192, 8191),  # k indivisible -> 2D masked
]
# Documented differential tolerances vs the float64 engine.  Observed
# worst relative cases are ~3e-7 (f32) and ~2e-2 (bf16 p99); the bounds
# leave room for platform-dependent fma/rounding without masking real
# regressions.  bf16 additionally gets an absolute floor: on
# sub-millisecond ragged totals its ~2^-8 step eps can compound to
# ~17% relative while staying below 0.1 ms absolute.
RTOL = {"float32": 1e-4, "bfloat16": 5e-2}
ATOL = {"float32": 0.0, "bfloat16": 1e-4}


def _zoo_batch() -> ScenarioBatch:
    gemms = ZOO + [s.gemm for s in TABLE_I]
    return ScenarioBatch(
        m=np.asarray([g.m for g in gemms]),
        n=np.asarray([g.n for g in gemms]),
        k=np.asarray([g.k for g in gemms]),
        dtype_bytes=np.asarray([g.dtype_bytes for g in gemms]),
    )


class TestMixedEngineRegistry:
    def test_registered_with_capability_flags(self):
        assert "mixed" in engine_names()
        eng = get_engine("mixed")
        assert eng.name == "mixed"
        assert eng.supports_ragged is True
        assert eng.jit is True
        # Honest flags: reduced-precision totals are not differentiable
        # calibration targets, and the engine manages its own x64 scope.
        assert eng.differentiable is False
        assert eng.trace_safe is False

    def test_dtype_validated(self):
        from repro.core.engine import MixedEngine

        with pytest.raises(ValueError, match="float16"):
            MixedEngine(dtype="float16")


class TestMixedDifferential:
    def test_float64_bit_identical_to_jax_engine(self):
        from repro.core.engine import MixedEngine

        sb = _zoo_batch()
        ref = get_engine("jax").evaluate(sb, MACHINES)
        got = MixedEngine(dtype="float64").evaluate(sb, MACHINES)
        assert_grid_identical(got, ref)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_uniform_zoo_within_tolerance(self, dtype):
        from repro.core.engine import MixedEngine

        sb = _zoo_batch()
        ref = get_engine("jax").evaluate(sb, MACHINES)
        got = MixedEngine(dtype=dtype).evaluate(sb, MACHINES)
        # Valid masks are integer logic: exactly equal at any dtype.
        assert np.array_equal(got.valid, ref.valid)
        a = got.total[got.valid]
        b = ref.total[ref.valid]
        assert np.allclose(a, b, rtol=RTOL[dtype], atol=0.0)
        # Exposed-comm decomposition tracks too (atol guards the
        # fully-hidden entries where exposed == 0).
        ea, eb = got.exposed[got.valid], ref.exposed[ref.valid]
        assert np.allclose(
            ea, eb, rtol=RTOL[dtype], atol=RTOL[dtype] * np.abs(b).max()
        )

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_ragged_within_tolerance(self, dtype):
        from repro.core.engine import MixedEngine
        from repro.sweep import device_ragged_batch

        rb = device_ragged_batch(48, seed=5)
        ref = get_engine("jax").evaluate(rb, MACHINES)
        got = MixedEngine(dtype=dtype).evaluate(rb, MACHINES)
        assert np.array_equal(got.valid, ref.valid)
        a, b = got.total[got.valid], ref.total[ref.valid]
        assert np.allclose(a, b, rtol=RTOL[dtype], atol=ATOL[dtype])


class TestDeviceSynthParity:
    def test_uniform_host_equals_device(self):
        from repro.sweep import device_batch, host_batch

        hb = host_batch(512, seed=9)
        db = device_batch(512, seed=9)
        for f in ("m", "n", "k", "dtype_bytes"):
            assert np.array_equal(getattr(hb, f), getattr(db, f)), f

    def test_ragged_host_equals_device(self):
        from repro.sweep import device_ragged_batch, host_ragged_batch

        hb = host_ragged_batch(256, seed=4)
        db = device_ragged_batch(256, seed=4)
        for f in ("m", "n", "k", "dtype_bytes"):
            assert np.array_equal(getattr(hb, f), getattr(db, f)), f
        # Masked tails are exact; interior fractions agree to f64
        # rounding (host and device sum/normalize in different orders).
        assert np.array_equal(hb.frac == 0.0, db.frac == 0.0)
        assert np.allclose(hb.frac, db.frac, rtol=0, atol=1e-14)

    def test_shard_composability(self):
        """host_batch(k, start=s) is rows [s, s+k) of host_batch(s+k) —
        the property that lets every shard regenerate its own lanes."""
        from repro.sweep import host_batch, host_ragged_batch

        full = host_batch(96, seed=2)
        part = host_batch(32, seed=2, start=48)
        for f in ("m", "n", "k", "dtype_bytes"):
            assert np.array_equal(
                getattr(full, f)[48:80], getattr(part, f)
            ), f
        rfull = host_ragged_batch(64, seed=2)
        rpart = host_ragged_batch(16, seed=2, start=24)
        assert np.array_equal(rfull.frac[24:40], rpart.frac)

    def test_seed_and_field_decorrelation(self):
        from repro.sweep import host_batch

        a = host_batch(256, seed=0)
        b = host_batch(256, seed=1)
        assert not np.array_equal(a.m, b.m)
        assert not np.array_equal(a.m, a.k)


class TestFusedStats:
    def test_float64_fused_equals_host_sweep_stats(self):
        """The tentpole parity: on-device synth + eval + stats at
        float64 is bit-identical to the host reduce-mode pipeline on
        the same lanes."""
        from repro.learn.stats import sweep_stats
        from repro.sweep import host_batch
        from repro.sweep.device import sweep_device_stats

        S = 1024
        dev, dres = sweep_device_stats(
            S, MACHINES, seed=3, dtype="float64", num_shards=2
        )
        host, hres = sweep_stats(
            host_batch(S, seed=3), MACHINES, backend="jax", num_shards=2
        )
        assert np.array_equal(dev.hist, host.hist)
        assert dev.n_points == host.n_points
        assert dev.best_counts == host.best_counts
        # Shard summaries carry the same tallies.
        assert [s.best_counts for s in dres.summaries] == [
            s.best_counts for s in hres.summaries
        ]

    def test_float64_fused_equals_host_sweep_stats_ragged(self):
        from repro.learn.stats import sweep_stats
        from repro.sweep import host_ragged_batch
        from repro.sweep.device import sweep_device_stats

        S = 512
        dev, _ = sweep_device_stats(
            S, MACHINES, seed=6, dtype="float64", ragged=True,
            num_shards=2,
        )
        host, _ = sweep_stats(
            host_ragged_batch(S, seed=6), MACHINES, backend="jax",
            num_shards=2,
        )
        assert np.array_equal(dev.hist, host.hist)
        assert dev.best_counts == host.best_counts

    def test_float32_fused_equals_own_grid_stats(self):
        """Same-dtype twin: the fused f32 statistics are exactly the
        statistics of the f32 grid the mixed engine materializes — the
        histogram's feature/score binning is f64 on both sides, so
        reduced precision moves regret columns only through the times,
        never through the binning."""
        from repro.core.engine import MixedEngine
        from repro.learn.stats import GateStats
        from repro.sweep import device_batch
        from repro.sweep.device import sweep_device_stats

        S = 1024
        dev, _ = sweep_device_stats(S, MACHINES, seed=3, dtype="float32")
        grid = MixedEngine(dtype="float32").evaluate(
            device_batch(S, seed=3), MACHINES
        )
        host = GateStats.from_grid(grid)
        assert np.array_equal(dev.hist, host.hist)
        assert dev.best_counts == host.best_counts

    def test_per_family_partitions_global(self):
        from repro.sweep.device import sweep_device_stats

        S = 1024
        fams, _ = sweep_device_stats(
            S, MACHINES, seed=3, dtype="float32", per_family=True
        )
        glob, _ = sweep_device_stats(S, MACHINES, seed=3, dtype="float32")
        assert set(fams) == {"mi300x-8", "tpu-v5e-axis16"}
        summed = None
        for st in fams.values():
            summed = st if summed is None else summed + st
        assert np.array_equal(summed.hist, glob.hist)
        assert summed.n_points == glob.n_points
        assert summed.best_counts == glob.best_counts

    def test_overlap_dispatch_changes_nothing(self):
        from repro.sweep.device import sweep_device_stats

        S = 1024
        on, ron = sweep_device_stats(
            S, MACHINES, seed=3, dtype="float32", num_shards=4,
            overlap_dispatch=True,
        )
        off, roff = sweep_device_stats(
            S, MACHINES, seed=3, dtype="float32", num_shards=4,
            overlap_dispatch=False,
        )
        assert np.array_equal(on.hist, off.hist)
        assert on.best_counts == off.best_counts
        assert [s.shard for s in ron.summaries] == [
            s.shard for s in roff.summaries
        ]
        assert [s.best_counts for s in ron.summaries] == [
            s.best_counts for s in roff.summaries
        ]

    def test_collect_stats_off_returns_none(self):
        from repro.sweep.device import sweep_device_stats

        stats, res = sweep_device_stats(
            1024, MACHINES, seed=3, dtype="float32", collect_stats=False
        )
        assert stats is None
        assert sum(s.n_scenarios for s in res.summaries) == 1024


class TestGateStability:
    def test_mixed_trained_gate_reproduces_float64(self):
        """Acceptance contract: training from float32 device statistics
        yields the float64 gate's tree — identical structure and split
        edges, leaf thresholds within one score-bin quantum (equal in
        practice; counts are exactly equal because binning is f64 on
        both sides)."""
        from repro.learn.gate import _THRESHOLDS, train_gate_from_stats
        from repro.sweep.device import sweep_device_stats

        S = 32768
        s32, _ = sweep_device_stats(S, MACHINES, dtype="float32")
        s64, _ = sweep_device_stats(S, MACHINES, dtype="float64")
        g32 = train_gate_from_stats(s32)
        g64 = train_gate_from_stats(s64)

        def walk(a, b):
            assert a.get("leaf") == b.get("leaf")
            if a.get("leaf"):
                assert a["n"] == b["n"]
                ia = _THRESHOLDS.index(a["gate"])
                ib = _THRESHOLDS.index(b["gate"])
                assert abs(ia - ib) <= 1, (a["gate"], b["gate"])
                return
            assert a["feature"] == b["feature"]
            assert a["edge"] == b["edge"]
            walk(a["lo"], b["lo"])
            walk(a["hi"], b["hi"])

        assert g32.n_leaves == g64.n_leaves
        walk(g32.tree, g64.tree)


class TestRunnerOverlap:
    def test_numpy_engine_flag_is_inert(self):
        """overlap_dispatch on a single-phase engine falls back to the
        eager path bit-for-bit (gather mode compares full grids)."""
        from repro.sweep import sweep_grid, synthetic_batch

        sb = synthetic_batch(300, seed=1)
        on = sweep_grid(
            sb, MACHINES, num_shards=5, mode="gather",
            overlap_dispatch=True,
        )
        off = sweep_grid(sb, MACHINES, num_shards=5, mode="gather")
        assert_grid_identical(on.grid, off.grid)

        def stable(s):
            # Everything but the wall-clock fields is deterministic.
            d = s.to_json()
            d.pop("seconds"), d.pop("scenarios_per_sec")
            return d

        assert [stable(s) for s in on.summaries] == [
            stable(s) for s in off.summaries
        ]

    def test_mixed_engine_two_phase_identical(self):
        from repro.core.engine import MixedEngine
        from repro.sweep import device_batch, sweep_grid

        sb = device_batch(512, seed=7)
        eng = MixedEngine(dtype="float32")
        on = sweep_grid(
            sb, MACHINES, engine=eng, num_shards=4, mode="gather",
            overlap_dispatch=True,
        )
        off = sweep_grid(sb, MACHINES, engine=eng, num_shards=4,
                         mode="gather")
        assert_grid_identical(on.grid, off.grid)

    def test_empty_shards_keep_summary_order(self):
        from repro.sweep import device_batch, sweep_grid

        sb = device_batch(3, seed=0)
        res = sweep_grid(
            sb, MACHINES, num_shards=6, mode="reduce",
            overlap_dispatch=True,
        )
        assert [s.shard for s in res.summaries] == list(range(6))
        assert sum(s.n_scenarios for s in res.summaries) == 3


class TestClosedFormPipeline:
    def test_matches_scan_and_never_flips_argmin(self):
        from repro.autotune import jaxgrid
        from repro.sweep import host_batch

        sb = host_batch(2048, seed=13)
        import jax
        import jax.numpy as jnp

        with jax.enable_x64(True):
            mp = jaxgrid.machine_arrays(MACHINES)
            g_max = max(m.group for m in MACHINES)
            scan = jaxgrid.evaluate_grid_raw(sb, mp, g_max=g_max)
            closed = jaxgrid.evaluate_grid_raw(
                sb, mp, g_max=g_max, closed_form=True
            )
        # Raw layout: (total, comm_busy, compute_busy, exposed, steps,
        # valid, ...), machine-major (M, L, S).
        t_s, t_c = np.asarray(scan[0]), np.asarray(closed[0])
        v_s, v_c = np.asarray(scan[5]), np.asarray(closed[5])
        assert np.array_equal(v_s, v_c)
        a, b = t_c[v_c], t_s[v_s]
        denom = np.where(b == 0.0, 1.0, np.abs(b))
        assert np.nanmax(np.abs(a - b) / denom) < 1e-12
        # Ranking is untouched: same argmin on every (machine, lane).
        ts = np.where(v_s, t_s, np.inf)
        tc = np.where(v_c, t_c, np.inf)
        assert np.array_equal(
            np.argmin(ts, axis=1), np.argmin(tc, axis=1)
        )

    def test_floor_div_exact(self):
        import jax
        import jax.numpy as jnp

        from repro.autotune.jaxgrid import _floor_div

        rng = np.random.default_rng(0)
        a = rng.integers(0, 1 << 26, size=4096).astype(np.int64)
        b = rng.integers(1, 1 << 20, size=4096).astype(np.int64)
        with jax.enable_x64(True):
            got = np.asarray(_floor_div(jnp.asarray(a), jnp.asarray(b)))
            assert np.array_equal(got, a // b)
            # The negated-ceil pattern: -_floor_div(-a, b) == ceil(a/b).
            ceil = np.asarray(
                -_floor_div(jnp.asarray(-a), jnp.asarray(b))
            )
            assert np.array_equal(ceil, -((-a) // b))


def test_sweep_cli_mixed_dtype_and_synth_device(tmp_path):
    """scripts/sweep.py drives the mixed engine end-to-end: --dtype
    rides --backend mixed (and is rejected otherwise), --synth-device
    swaps in the counter-based stream, and the host summary records
    both so merge_sweep.py can enforce no-silent-mixing."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )

    def run(*args):
        return subprocess.run(
            [sys.executable, str(root / "scripts" / "sweep.py"), *args],
            capture_output=True, text=True, timeout=600, env=env,
        )

    out = tmp_path / "sweep.jsonl"
    proc = run(
        "--scenarios", "64", "--shards", "2", "--mode", "reduce",
        "--backend", "mixed", "--dtype", "float32", "--synth-device",
        "--overlap-dispatch", "--out", str(out),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    host = [
        json.loads(ln)["host_summary"]
        for ln in out.read_text().splitlines()
        if "host_summary" in ln
    ]
    assert len(host) == 1
    assert host[0]["dtype"] == "float32"
    assert host[0]["synth"] == "device"
    assert host[0]["n_scenarios"] == 64

    # Reduced precision without the mixed engine is a usage error.
    proc = run("--scenarios", "8", "--dtype", "bfloat16")
    assert proc.returncode == 2
    assert "requires --backend mixed" in proc.stderr


class TestDeviceMergeStats:
    """device_merge_stats: on-device multi-host GateStats reduction."""

    def _stats_list(self, n=3):
        from repro.learn import sweep_stats
        from repro.sweep import synthetic_ragged_batch

        return [
            sweep_stats(
                synthetic_ragged_batch(60, seed=40 + i),
                MACHINES[:2],
                num_shards=2,
            )[0]
            for i in range(n)
        ]

    def test_bit_identical_to_host_fold(self):
        import functools

        from repro.learn import GateStats
        from repro.sweep import device_merge_stats

        stats = self._stats_list(3)
        got = device_merge_stats(stats)
        want = functools.reduce(GateStats.merge, stats)
        assert np.array_equal(got.hist, want.hist)
        assert np.array_equal(got.moments, want.moments)
        assert got.best_counts == want.best_counts
        assert got.n_points == want.n_points
        assert got.schema == want.schema

    def test_single_and_empty_inputs(self):
        from repro.learn import GateStats
        from repro.sweep import device_merge_stats

        (only,) = self._stats_list(1)
        got = device_merge_stats([only])  # pmap path on 1 device
        assert np.array_equal(got.hist, only.hist)
        assert got.n_points == only.n_points
        empty = device_merge_stats([])
        assert empty.n_points == 0
        assert np.array_equal(empty.hist, GateStats.empty().hist)

    def test_schema_mismatch_rejected(self):
        import dataclasses

        from repro.sweep import device_merge_stats

        a, b, _ = self._stats_list(3)
        bad = dataclasses.replace(b, schema=b.schema + 1)
        with pytest.raises(ValueError, match="schema"):
            device_merge_stats([a, bad])
