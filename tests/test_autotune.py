"""repro.autotune: jitted grid engine == NumPy engine, differentiable
TAU calibration, tiered tuner, persistent cache, serial gate.

Equivalence is randomized (seeded) over the scenario grid x machine grid
— all schedules, both topologies, group sizes 8/16, dtypes bf16/fp8/fp32
— asserting the jax engine matches ``repro.core.batch.evaluate_grid``
within 1e-5 relative (measured agreement is ~1e-15: the jitted scan
replays the NumPy accumulation order in float64).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    GRID_SCHEDULES,
    MI300X,
    TABLE_I,
    TPU_V5E,
    GemmShape,
    Schedule,
    ScenarioBatch,
    machine_grid,
    scenario_grid,
)
from repro.core.batch import evaluate_grid as np_evaluate_grid

pytestmark = pytest.mark.autotune

RTOL = 1e-5
_FIELDS = ("total", "comm_busy", "compute_busy", "exposed")


def _grid_slice(seed: int, count: int):
    scenarios = scenario_grid()
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(scenarios), size=count, replace=False)
    return [scenarios[i] for i in idx]


def _assert_engines_agree(scenarios, machines, **kw):
    from repro.autotune import evaluate_grid_jax
    from repro.core.batch import _as_batch

    sb = _as_batch(scenarios)
    ref = np_evaluate_grid(sb, machines, **kw)
    got = evaluate_grid_jax(sb, machines, **kw)
    assert (ref.valid == got.valid).all()
    assert (ref.steps == got.steps).all()
    for f in _FIELDS:
        a, b = getattr(ref, f), getattr(got, f)
        assert np.isnan(b[~ref.valid]).all(), f
        np.testing.assert_allclose(
            b[ref.valid], a[ref.valid], rtol=RTOL, err_msg=f
        )
    np.testing.assert_allclose(got.serial_comm, ref.serial_comm, rtol=RTOL)
    np.testing.assert_allclose(got.serial_gemm, ref.serial_gemm, rtol=RTOL)
    assert (ref.best_idx() == got.best_idx()).all()


class TestJaxNumpyEquivalence:
    def test_table_i_dma_on_off(self):
        for dma in (True, False):
            _assert_engines_agree(
                list(TABLE_I), (MI300X, TPU_V5E), dma=dma
            )

    def test_random_grid_slice_all_topologies(self):
        """Random grid slice x full machine grid (both topologies, mixed
        group sizes vmapped together through the padded scan)."""
        _assert_engines_agree(_grid_slice(seed=42, count=32), machine_grid())

    def test_full_acceptance_grid(self):
        """The acceptance criterion verbatim: the full 720-scenario x
        8-machine grid agrees within 1e-5 relative tolerance."""
        scenarios = scenario_grid()
        machines = machine_grid()
        assert len(scenarios) == 720 and len(machines) == 8
        _assert_engines_agree(scenarios, machines)

    def test_schedule_subsets(self):
        subset = (Schedule.SERIAL, Schedule.UNIFORM_FUSED_1D)
        _assert_engines_agree(
            list(TABLE_I)[:6], (MI300X,), schedules=subset
        )
        subset = (Schedule.SHARD_P2P, Schedule.HETERO_UNFUSED_1D)
        _assert_engines_agree(
            list(TABLE_I)[:6], (TPU_V5E,), schedules=subset
        )

    def test_extra_dtypes(self):
        """fp8 / bf16 / fp32 operand widths all agree."""
        gemms = [
            GemmShape(65536, 8192, 8192, b) for b in (1, 2, 4)
        ] + [GemmShape(131072, 4096, 16384, 4)]
        from repro.autotune import evaluate_grid_jax

        ref = np_evaluate_grid(gemms, (MI300X, TPU_V5E))
        got = evaluate_grid_jax(gemms, (MI300X, TPU_V5E))
        np.testing.assert_allclose(
            got.total[ref.valid], ref.total[ref.valid], rtol=RTOL
        )

    def test_dma_into_place(self):
        _assert_engines_agree(
            list(TABLE_I)[:8], (MI300X,), dma_into_place=True
        )

    def test_degenerate_and_indivisible_masked(self):
        """NaN/validity handling matches the NumPy engine exactly."""
        gemms = [
            GemmShape(1001, 4096, 4096),  # m not divisible by any group
            GemmShape(32, 4096, 4096),  # hetero chunk rows would be 0
            GemmShape(8192, 8192, 8191),  # k indivisible -> 2D masked
        ]
        _assert_engines_agree(gemms, (MI300X, TPU_V5E))

    def test_backend_switch(self):
        from repro.autotune import evaluate_grid

        a = evaluate_grid(list(TABLE_I)[:4], (MI300X,), backend="numpy")
        b = evaluate_grid(list(TABLE_I)[:4], (MI300X,), backend="jax")
        np.testing.assert_allclose(
            b.total[a.valid], a.total[a.valid], rtol=RTOL
        )
        with pytest.raises(ValueError):
            evaluate_grid(list(TABLE_I)[:4], (MI300X,), backend="torch")


class TestDifferentiability:
    def test_grad_total_wrt_tau_finite_nonzero(self):
        """d E[heuristic-picked time] / d tau exists and is informative."""
        import jax
        import jax.numpy as jnp

        from repro.autotune import expected_heuristic_time

        with jax.enable_x64(True):
            f = lambda t: expected_heuristic_time(t, TABLE_I, MI300X)
            g = jax.grad(f)(jnp.asarray(0.02, jnp.float64))
        assert np.isfinite(float(g))
        assert float(g) != 0.0

    def test_grad_wrt_machine_params_finite_nonzero(self):
        """The grid is differentiable through machine parameters: a
        faster HBM strictly reduces mean schedule time."""
        import jax
        import jax.numpy as jnp

        from repro.autotune import evaluate_grid_raw, machine_arrays

        with jax.enable_x64(True):
            mp = machine_arrays((MI300X,))

            def mean_total(link_bw):
                out = evaluate_grid_raw(
                    list(TABLE_I)[:4],
                    mp._replace(link_bw=link_bw),
                    g_max=MI300X.group,
                )
                total, valid = out[0], out[5]
                return jnp.sum(jnp.where(valid, total, 0.0))

            g = jax.grad(mean_total)(mp.link_bw)
        assert np.isfinite(np.asarray(g)).all()
        assert float(np.asarray(g)[0]) < 0.0  # faster links -> less time

    def test_calibrate_tau_matches_bisection_within_5pct(self):
        """Acceptance: a few Adam steps reproduce the bisection TAU on
        MI300X within 5% (and land at a no-worse loss)."""
        from repro.autotune import (
            calibrate_tau,
            calibrate_tau_reference,
            expected_heuristic_time,
        )

        tau_ref = calibrate_tau_reference(MI300X, TABLE_I)
        tau_adam = calibrate_tau(MI300X, TABLE_I)
        assert abs(tau_adam - tau_ref) / tau_ref < 0.05
        l_ref = float(expected_heuristic_time(tau_ref, TABLE_I, MI300X))
        l_adam = float(expected_heuristic_time(tau_adam, TABLE_I, MI300X))
        assert l_adam <= l_ref * (1.0 + 1e-6)

    def test_calibrated_tau_no_worse_than_discrete_search(self):
        """Hard-decision accuracy with the gradient TAU is at least the
        discrete candidate search's (the engine it replaces)."""
        from repro.core.explorer import explore_grid
        from repro.core.heuristics import _TAU_OVERRIDES, calibrate_tau
        from repro.autotune import calibrate_tau as grad_tau

        saved = _TAU_OVERRIDES.pop(MI300X.name, None)
        try:
            disc = calibrate_tau(MI300X, TABLE_I)
            _TAU_OVERRIDES.pop(MI300X.name, None)
        finally:
            if saved is not None:
                _TAU_OVERRIDES[MI300X.name] = saved
        adam = grad_tau(MI300X, TABLE_I)
        acc_disc = explore_grid(
            TABLE_I, machines=(MI300X,), tau=disc
        ).accuracy(0.05)
        acc_adam = explore_grid(
            TABLE_I, machines=(MI300X,), tau=adam
        ).accuracy(0.05)
        assert acc_adam >= acc_disc - 1e-9


class TestSerialGate:
    def test_gridwide_within5_above_baseline(self):
        """Regression pin for the learned serial gate: grid-wide
        within-5% accuracy with the frozen gate clears 70%, against a
        gate-less baseline of ~30% (the PR-1 'serial tranche' finding).
        """
        from repro.core import explore_grid

        sb = ScenarioBatch.from_scenarios(scenario_grid())
        machines = machine_grid()
        gated = explore_grid(sb, machines=machines).accuracy(0.05)
        baseline = 0.31  # measured pre-gate (PR-1 engine, frozen pin)
        assert gated >= 0.70, f"gated accuracy regressed: {gated:.3f}"
        assert gated > baseline + 0.25

    def test_gate_disabled_reproduces_paper_tree(self):
        from repro.core import select_schedule

        gemm = GemmShape(65536, 2048, 8192)
        with_gate = select_schedule(gemm, TPU_V5E)
        without = select_schedule(gemm, TPU_V5E, serial_gate=np.inf)
        # This shape is comm-bound on the torus: gate says serial, the
        # paper tree decomposes.
        assert with_gate.schedule is Schedule.SERIAL
        assert without.schedule is not Schedule.SERIAL

    def test_batch_matches_scalar_with_gate(self):
        from repro.core import select_schedule, select_schedule_batch
        from repro.core.batch import GRID_SCHEDULES as GS

        scenarios = [*TABLE_I, *_grid_slice(seed=11, count=48)]
        sb = ScenarioBatch.from_scenarios(scenarios)
        for machine in (MI300X, TPU_V5E):
            picks = select_schedule_batch(
                sb.m, sb.n, sb.k, sb.dtype_bytes, machine
            )
            for i, sc in enumerate(scenarios):
                dec = select_schedule(sc.gemm, machine)
                assert GS[int(picks[i])] is dec.schedule, sc.name

    def test_calibrate_serial_gate(self):
        from repro.core.heuristics import (
            _SERIAL_GATE_OVERRIDES,
            calibrate_serial_gate,
        )

        cands = (0.5, 1.2, 5.0)
        got = calibrate_serial_gate(
            (MI300X,), _grid_slice(seed=3, count=64), candidates=cands
        )
        assert got in cands
        saved = dict(_SERIAL_GATE_OVERRIDES)
        try:
            calibrate_serial_gate(
                (MI300X,), _grid_slice(seed=3, count=64),
                candidates=cands, freeze=True,
            )
            assert MI300X.name in _SERIAL_GATE_OVERRIDES
        finally:
            _SERIAL_GATE_OVERRIDES.clear()
            _SERIAL_GATE_OVERRIDES.update(saved)


class TestTunerAndCache:
    def test_pick_analytic_then_cached(self):
        from repro.autotune import Autotuner

        t = Autotuner()
        gemm = GemmShape(65536, 8192, 8192)
        d1 = t.pick(gemm, MI300X)
        assert d1.source == "analytic"
        d2 = t.pick(gemm, MI300X)
        assert d2.source == "cache" and d2.schedule is d1.schedule
        assert t.hit_rate == pytest.approx(0.5)

    def test_analytic_pick_is_model_optimal(self):
        from repro.autotune import Autotuner

        t = Autotuner(backend="numpy")
        for sc in list(TABLE_I)[:6]:
            d = t.pick(sc.gemm, MI300X)
            grid = np_evaluate_grid([sc.gemm], (MI300X,))
            best = GRID_SCHEDULES[int(grid.best_idx()[0, 0])]
            assert d.schedule is best, sc.name

    def test_persisted_across_tuner_instances(self):
        from repro.autotune import Autotuner, default_cache_path

        gemm = GemmShape(131072, 16384, 16384)
        t1 = Autotuner()
        d1 = t1.pick(gemm, TPU_V5E, group=16)
        assert os.path.exists(default_cache_path())
        t2 = Autotuner()  # fresh instance, same backing file
        d2 = t2.pick(gemm, TPU_V5E, group=16)
        assert d2.source == "cache" and d2.schedule is d1.schedule

    def test_cache_corrupt_file_tolerated(self):
        from repro.autotune import AutotuneCache, default_cache_path

        path = default_cache_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write("{not json")
        c = AutotuneCache()
        assert len(c) == 0
        c.put("k", {"schedule": "serial", "source": "analytic"})
        assert len(AutotuneCache()) == 1  # healthy again

    def test_pick_never_records_unexecutable_schedule(self):
        """The cost model's validity (M % g == 0) is weaker than the
        runtime chunking rule (M/g % g == 0 for 1D FiCCO): the recorded
        winner must be one ``ficco_linear`` will actually run."""
        from repro.autotune import Autotuner
        from repro.overlap.api import _divisible

        gemm = GemmShape(65544, 8192, 8192)  # m%8==0 but (m/8)%8 != 0
        t = Autotuner()
        d = t.pick(gemm, MI300X)
        assert d.source == "analytic"
        assert _divisible(gemm.m // 8, gemm.k, 8, d.schedule)
        assert d.schedule not in (
            Schedule.UNIFORM_FUSED_1D,
            Schedule.HETERO_FUSED_1D,
            Schedule.HETERO_UNFUSED_1D,
        )

    def test_resolve_auto_respects_group(self):
        """schedule="auto" evaluates the tree (incl. the group-sensitive
        serial gate) at the actual axis size, not the machine default."""
        from repro.core import machine_for_group, select_schedule
        from repro.overlap.api import resolve_schedule

        for group in (4, 8):
            for m, n, k in ((8192, 16384, 16384), (65536, 2048, 8192)):
                want = select_schedule(
                    GemmShape(m, n, k), machine_for_group(TPU_V5E, group)
                ).schedule
                got = resolve_schedule(
                    "auto", m=m, n=n, k=k, group=group
                )
                assert got is want, (group, m, n, k)

    def test_concurrent_caches_merge_on_save(self):
        """Two processes tuning disjoint keys must not clobber each
        other: save() folds in entries persisted since our load."""
        from repro.autotune import AutotuneCache

        a = AutotuneCache()
        b = AutotuneCache()
        a.put("key/a", {"schedule": "serial", "source": "analytic"})
        b.put("key/b", {"schedule": "serial", "source": "analytic"})
        fresh = AutotuneCache()
        assert "key/a" in fresh and "key/b" in fresh

    def test_cache_jax_version_mismatch_invalidates(self):
        from repro.autotune import AutotuneCache, default_cache_path

        c = AutotuneCache()
        c.put("k", {"schedule": "serial", "source": "analytic"})
        with open(default_cache_path()) as f:
            raw = json.load(f)
        raw["jax"] = "0.0.0-other"
        with open(default_cache_path(), "w") as f:
            json.dump(raw, f)
        assert len(AutotuneCache()) == 0

    def test_measured_tier_records_winner(self):
        import jax
        import jax.numpy as jnp

        from repro.autotune import Autotuner

        mesh = jax.make_mesh((1,), ("tp",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
        t = Autotuner()
        d = t.measure(
            x, w, mesh=mesh, axis_name="tp", machine=TPU_V5E,
            schedules=[Schedule.SERIAL], iters=1,
        )
        assert d.source == "measured"
        assert d.schedule is Schedule.SERIAL
        assert d.measured_total_s is not None and d.measured_total_s > 0
        # tier-1 lookup now prefers the measured record
        gemm = GemmShape(64, 16, 32, x.dtype.itemsize)
        d2 = t.pick(gemm, TPU_V5E, group=1)
        assert d2.source == "cache" and d2.schedule is Schedule.SERIAL

    def test_resolve_schedule_autotune_and_fallback(self):
        from repro.overlap.api import resolve_schedule

        s = resolve_schedule(
            "autotune", m=65536, n=8192, k=8192, machine=MI300X, group=8
        )
        assert isinstance(s, Schedule)
        grid = np_evaluate_grid([GemmShape(65536, 8192, 8192)], (MI300X,))
        assert s is GRID_SCHEDULES[int(grid.best_idx()[0, 0])]

    def test_resolve_schedule_counts_tuner_fallback(self, monkeypatch):
        """A shape the tuner's model cannot rank resolves through the
        static tree, counted as ``autotune_fallback``."""
        from repro.autotune import Autotuner, get_tuner
        from repro.core import select_schedule
        from repro.obs import metrics
        from repro.overlap.api import resolve_schedule

        def unrankable(self, *a, **kw):
            raise ValueError("no valid schedule")

        monkeypatch.setattr(Autotuner, "executable_ranking", unrankable)
        fallback = metrics.get_metrics().counter(
            "overlap/resolve.autotune_fallback"
        )
        before = fallback.value
        gemm = GemmShape(65536, 8192, 8192)
        s = resolve_schedule(
            "autotune", m=gemm.m, n=gemm.n, k=gemm.k, machine=MI300X,
            group=MI300X.group,
        )
        assert fallback.value == before + 1
        assert get_tuner().pick(gemm, MI300X).source == "heuristic"
        assert s is select_schedule(gemm, MI300X).schedule

    def test_resolve_schedule_autotune_raises_tuner_errors(self, monkeypatch):
        """Errors other than the tuner's own "no answer" propagate."""
        import repro.autotune
        from repro.overlap.api import resolve_schedule

        def broken():
            raise ImportError("tuner unavailable")

        monkeypatch.setattr(repro.autotune, "get_tuner", broken)
        with pytest.raises(ImportError):
            resolve_schedule("autotune", m=65536, n=8192, k=8192, group=8)


class TestCacheSchemaV2:
    """Schema v2: the ragged step-profile digest joined the key schema
    (ISSUE 3).  v1 stores written by PR 2 must be invalidated cleanly —
    no KeyError on old entries, no old decision surfacing under a new
    key — and the clear script must handle both file names."""

    def test_schema_and_default_path_bumped(self):
        from repro.autotune import SCHEMA_VERSION, default_cache_path

        assert SCHEMA_VERSION == 2
        assert default_cache_path().endswith("autotune-v2.json")

    def _write_v1_store(self, directory):
        """A realistic PR-2-era store: v1 schema, profile-less keys."""
        import jax

        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, "autotune-v1.json")
        with open(path, "w") as f:
            json.dump(
                {
                    "schema": 1,
                    "jax": jax.__version__,
                    "entries": {
                        "mi300x-8/g8/m65536/n8192/k8192/b2": {
                            "schedule": "hetero-fused-1d",
                            "source": "measured",
                            "model_total_s": None,
                            "measured_total_s": 1e-9,  # poisoned-fast
                        }
                    },
                },
                f,
            )
        return path

    def test_v1_store_invalidated_cleanly(self):
        """A v1 file on disk never feeds a v2 tuner: the tuner starts
        cold (no KeyError, no stale decision) and re-tunes under the
        profile-suffixed key."""
        from repro.autotune import Autotuner, AutotuneCache

        cache_dir = os.environ["REPRO_AUTOTUNE_CACHE_DIR"]
        self._write_v1_store(cache_dir)
        c = AutotuneCache()
        assert len(c) == 0  # old entries invisible, not an error
        t = Autotuner(cache=c)
        gemm = GemmShape(65536, 8192, 8192)
        d = t.pick(gemm, MI300X)  # same site the v1 store "measured"
        assert d.source == "analytic"  # re-tuned, not the stale winner
        assert all(key.endswith("/u8") for key in c.entries)

    def test_v1_payload_at_v2_path_treated_as_empty(self):
        """Even a v1-schema payload written AT the v2 file name is
        rejected wholesale by the schema stamp."""
        from repro.autotune import AutotuneCache, default_cache_path

        import jax

        path = default_cache_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(
                {
                    "schema": 1,
                    "jax": jax.__version__,
                    "entries": {"old/key": {"schedule": "serial"}},
                },
                f,
            )
        assert len(AutotuneCache()) == 0

    def test_keys_carry_profile_digest(self):
        from repro.autotune import Autotuner, TuneKey
        from repro.core import StepProfile

        gemm = GemmShape(65536, 8192, 8192)
        assert str(TuneKey.for_gemm(gemm, MI300X)).endswith("/b2/u8")
        skew = StepProfile.skewed(8, 4.0)
        key = str(TuneKey.for_gemm(gemm, MI300X, profile=skew))
        assert key.endswith("/" + skew.digest())

        t = Autotuner(backend="numpy")
        d_uniform = t.pick(gemm, MI300X)
        d_skew = t.pick(gemm, MI300X, profile=skew)
        assert len(t.cache.entries) == 2  # distinct keys coexist
        assert d_uniform.source == "analytic"
        assert d_skew.source == "analytic"
        # both hit their own record on re-query
        assert t.pick(gemm, MI300X).source == "cache"
        assert t.pick(gemm, MI300X, profile=skew).source == "cache"

    def test_ragged_pick_not_filtered_by_uniform_runtime_rule(self):
        """Profile-keyed picks go to the ragged kernel path (arbitrary
        quantized chunk sizes), so ficco_linear's one-level-deeper
        divisibility filter must not apply: m=96, g=8 has m%g==0 but
        (m/g)%g!=0 — the uniform pick falls back to serial/p2p, while
        the ragged pick may keep the model's FiCCO winner."""
        from repro.autotune import Autotuner
        from repro.core import StepProfile
        from repro.core.batch import evaluate_ragged_grid, RaggedBatch
        from repro.core.workload import RaggedScenario

        gemm = GemmShape(65544, 8192, 8192)  # m%8==0 but (m/8)%8 != 0
        profile = StepProfile.skewed(8, 2.0)
        t = Autotuner(backend="numpy")
        d = t.pick(gemm, MI300X, profile=profile)
        rb = RaggedBatch.from_ragged_scenarios(
            [RaggedScenario("x", "EP", "t", gemm, profile)]
        )
        grid = evaluate_ragged_grid(rb, (MI300X,))
        best = GRID_SCHEDULES[int(grid.best_idx()[0, 0])]
        assert d.schedule is best  # the model optimum, unfiltered

    def test_padded_profile_shares_cache_key_with_trimmed(self):
        from repro.core import StepProfile

        p = StepProfile.skewed(5, 3.0)
        assert p.padded(9).digest() == p.digest()
        assert StepProfile.uniform(4).padded(8).digest() == "u4"

    def test_clear_script_handles_old_and_new_names(self, tmp_path):
        from repro.autotune import AutotuneCache

        cache_dir = str(tmp_path / "cc")
        v1 = self._write_v1_store(cache_dir)
        env = dict(os.environ, REPRO_AUTOTUNE_CACHE_DIR=cache_dir)
        c = AutotuneCache(path=os.path.join(cache_dir, "autotune-v2.json"))
        c.put("k/u8", {"schedule": "serial", "source": "analytic"})
        v2 = c.path
        assert os.path.exists(v1) and os.path.exists(v2)
        out = subprocess.run(
            [sys.executable, "scripts/clear_autotune_cache.py"],
            env=env, capture_output=True, text=True,
        )
        assert out.returncode == 0, out.stderr
        assert not os.path.exists(v1) and not os.path.exists(v2)


_ROUNDTRIP_SCRIPT = r"""
import functools, json, os, sys
import numpy as np
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.overlap import ficco_linear
from repro.autotune import get_tuner

mesh = jax.make_mesh((8,), ("tp",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
fn = jax.jit(
    jax.shard_map(
        functools.partial(ficco_linear, axis_name="tp", schedule="autotune"),
        mesh=mesh,
        in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"),
        check_vma=False,
    )
)
out = np.asarray(fn(x, w))
ok = np.allclose(out, np.asarray(x) @ np.asarray(w), rtol=1e-3, atol=1e-3)
t = get_tuner()
print(json.dumps({
    "ok": bool(ok), "hits": t.hits, "misses": t.misses,
    "entries": sorted(t.cache.entries),
    "schedules": [t.cache.entries[k]["schedule"]
                  for k in sorted(t.cache.entries)],
    "sources": [t.cache.entries[k]["source"]
                for k in sorted(t.cache.entries)],
}))
"""


@pytest.mark.slow
class TestFreshProcessRoundtrip:
    def test_ficco_linear_autotune_roundtrips_cache(self, tmp_path):
        """Acceptance: ``ficco_linear(schedule="autotune")`` persists its
        tuned decision and a *fresh process* answers from the cache."""
        env = dict(
            os.environ,
            PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""),
            REPRO_AUTOTUNE_CACHE_DIR=str(tmp_path / "cache"),
        )

        def run():
            p = subprocess.run(
                [sys.executable, "-c", _ROUNDTRIP_SCRIPT],
                capture_output=True, text=True, env=env,
                cwd=os.path.dirname(os.path.dirname(__file__)),
                timeout=600,
            )
            assert p.returncode == 0, p.stderr[-2000:]
            return json.loads(p.stdout.strip().splitlines()[-1])

        first = run()
        assert first["ok"]
        assert first["misses"] >= 1 and first["hits"] == 0
        assert first["entries"], "no cache entry persisted"
        assert all(s == "analytic" for s in first["sources"])

        second = run()
        assert second["ok"]
        assert second["hits"] >= 1 and second["misses"] == 0
        assert second["entries"] == first["entries"]
        assert second["schedules"] == first["schedules"]
