"""Differential test harness for the ragged-step (non-uniform) engines.

The tentpole contract: for ANY ragged step list, the three engines —
the scalar simulator (``simulate(..., profile=...)`` + the pure-Python
masked pipeline), the NumPy masked-scan engine
(``batch.evaluate_ragged_grid``) and the jitted engine
(``jaxgrid.evaluate_ragged_grid``) — agree on totals, busy times and
exposed comm to within 1e-12 relative (scalar vs NumPy are held to
1e-15: they share the per-step time model and differ only in their
pipeline scans).  Degenerate profiles are first-class: a single-step
profile (fully serialized), an all-masked tail (zero padding), extreme
skew (all mass in one chunk), and mixed-length batches.

The uniform path must be untouched: the uniform-schedule grid is pinned
bit-identical to pre-PR golden values, and a uniform profile pushed
through the ragged engines reproduces the uniform engine bit-for-bit.
"""

import numpy as np
import pytest

from repro.core import (
    GRID_SCHEDULES,
    MI300X,
    TABLE_I,
    TPU_V5E,
    GemmShape,
    RaggedBatch,
    RaggedScenario,
    ScenarioBatch,
    Schedule,
    StepProfile,
    evaluate_grid,
    machine_grid,
    ragged_scenario_grid,
    simulate,
)
from repro.core import batch as core_batch
from repro.core.simulator import _pipeline_masked

# Acceptance tolerance for the three-way engine agreement (the jitted
# engine recomputes every model in XLA; 1e-12 is the ISSUE's bar, actual
# agreement is ~1e-15).
RTOL = 1e-12
# Scalar and NumPy share the per-step time model; only their pipeline
# scans differ, and those replay each other's accumulation order.
RTOL_SCALAR_NP = 1e-15

FICCO = tuple(
    s for s in GRID_SCHEDULES
    if s not in (Schedule.SERIAL, Schedule.SHARD_P2P)
)

_FIELDS = {
    "total": "total",
    "comm_busy": "comm_busy",
    "compute_busy": "compute_busy",
    "exposed": "exposed_comm",
}


def _profiles():
    """The profile zoo: every degenerate the harness must pin down."""
    return [
        StepProfile.uniform(8),
        StepProfile.skewed(8, 2.0),
        StepProfile.skewed(8, 0.25),            # front-loaded
        StepProfile.skewed(16, 8.0),            # extreme geometric skew
        StepProfile.zipf(8, 1.0),
        StepProfile.top_k_hot(8, 2, 0.6),
        StepProfile((1.0,)),                    # S=1: fully serialized
        StepProfile((1.0, 0.0, 0.0, 0.0)),      # all mass in chunk 0
        StepProfile((0.0, 0.0, 0.0, 1.0)),      # all mass in the tail
        StepProfile.skewed(5, 0.5).padded(9),   # masked tail padding
    ]


def _ragged_set(seed=0, count=6):
    rng = np.random.default_rng(seed)
    ms = [8192, 65536, 131072, 262144, 1048576]
    ks = [4096, 8192, 16384]
    ns = [8192, 28672, 57344]
    out = []
    profiles = _profiles()
    for i in range(count):
        gemm = GemmShape(
            int(rng.choice(ms)), int(rng.choice(ns)), int(rng.choice(ks))
        )
        for p in profiles:
            out.append(RaggedScenario(f"r{i}/{p.name}", "EP", "t", gemm, p))
    return out


def _assert_three_way(scenarios, machines, *, dma=True, dma_into_place=False):
    from repro.autotune import jaxgrid

    rb = RaggedBatch.from_ragged_scenarios(scenarios)
    grid_np = core_batch.evaluate_ragged_grid(
        rb, machines, dma=dma, dma_into_place=dma_into_place
    )
    grid_jx = jaxgrid.evaluate_ragged_grid(
        rb, machines, dma=dma, dma_into_place=dma_into_place
    )
    for j, machine in enumerate(machines):
        for i, sc in enumerate(scenarios):
            for l, sched in enumerate(GRID_SCHEDULES):
                try:
                    want = simulate(
                        sc.gemm, machine, sched, profile=sc.profile,
                        dma=dma, dma_into_place=dma_into_place,
                    )
                except ValueError:
                    assert not grid_np.valid[l, i, j]
                    assert not grid_jx.valid[l, i, j]
                    assert np.isnan(grid_np.total[l, i, j])
                    continue
                assert grid_np.valid[l, i, j], (sched, sc.name, machine.name)
                assert grid_jx.valid[l, i, j], (sched, sc.name, machine.name)
                for fname, attr in _FIELDS.items():
                    ref = getattr(want, attr)
                    got_np = getattr(grid_np, fname)[l, i, j]
                    got_jx = getattr(grid_jx, fname)[l, i, j]
                    assert got_np == pytest.approx(
                        ref, rel=RTOL_SCALAR_NP, abs=1e-18
                    ), (fname, sched, sc.name, machine.name)
                    assert got_jx == pytest.approx(
                        ref, rel=RTOL, abs=1e-15
                    ), (fname, sched, sc.name, machine.name)


# ---------------------------------------------------------------------------
# Pipeline primitive: the masked ragged scan in all three engines.
# ---------------------------------------------------------------------------


class TestMaskedPipelinePrimitive:
    def _random_case(self, rng, n_steps, batch):
        comm = [np.abs(rng.standard_normal(batch)) for _ in range(n_steps)]
        compute = [np.abs(rng.standard_normal(batch)) for _ in range(n_steps)]
        comm_act = [rng.random(batch) > 0.3 for _ in range(n_steps)]
        comp_act = [rng.random(batch) > 0.3 for _ in range(n_steps)]
        deps = list(range(n_steps))
        return comm, compute, deps, comm_act, comp_act

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_three_way_random(self, seed):
        import jax
        import jax.numpy as jnp

        from repro.autotune.jaxgrid import pipeline_jax

        rng = np.random.default_rng(seed)
        for n_steps in (1, 2, 5):
            for deps_kind in ("chain", "local", "free"):
                comm, compute, deps, c_act, w_act = self._random_case(
                    rng, n_steps, batch=7
                )
                if deps_kind == "local":
                    compute = [np.abs(rng.standard_normal(7))] + compute
                    w_act = [np.ones(7, dtype=bool)] + w_act
                    deps = [None] + deps
                elif deps_kind == "free":
                    deps = [None] * n_steps
                got_np = core_batch.pipeline_vec(
                    comm, compute, deps, c_act, w_act
                )
                with jax.enable_x64(True):
                    got_jx = pipeline_jax(
                        [jnp.asarray(c) for c in comm],
                        [jnp.asarray(w) for w in compute],
                        deps,
                        [jnp.asarray(a) for a in c_act],
                        [jnp.asarray(a) for a in w_act],
                    )
                for b in range(7):
                    want = _pipeline_masked(
                        [float(c[b]) for c in comm],
                        [float(w[b]) for w in compute],
                        deps,
                        [bool(a[b]) for a in c_act],
                        [bool(a[b]) for a in w_act],
                    )
                    # (total, exposed, comm_busy, compute_busy)
                    for x, (w_np, w_jx) in zip(
                        want, zip(got_np, got_jx)
                    ):
                        assert float(w_np[b]) == pytest.approx(
                            x, rel=RTOL_SCALAR_NP, abs=1e-18
                        )
                        assert float(w_jx[b]) == pytest.approx(
                            x, rel=RTOL, abs=1e-15
                        )

    def test_masks_default_to_uniform_path(self):
        """pipeline_vec without masks == with all-True masks, bit-exact."""
        rng = np.random.default_rng(42)
        comm = [np.abs(rng.standard_normal(5)) for _ in range(4)]
        compute = [np.abs(rng.standard_normal(5)) for _ in range(4)]
        deps = list(range(4))
        ones = [np.ones(5, dtype=bool)] * 4
        a = core_batch.pipeline_vec(comm, compute, deps)
        b = core_batch.pipeline_vec(comm, compute, deps, ones, ones)
        for x, y in zip(a, b):
            assert (x == y).all()

    def test_inactive_steps_never_stall(self):
        """A masked compute step must not accrue exposed time even when
        its comm dependency would be 'late'."""
        comm = [np.array([10.0]), np.array([10.0])]
        compute = [np.array([1.0]), np.array([1.0])]
        deps = [0, 1]
        c_act = [np.array([True]), np.array([False])]
        w_act = [np.array([True]), np.array([False])]
        total, exposed, comm_sum, comp_sum = core_batch.pipeline_vec(
            comm, compute, deps, c_act, w_act
        )
        assert float(comm_sum[0]) == 10.0  # second comm masked
        assert float(exposed[0]) == 10.0  # only the first stall counts
        assert float(total[0]) == 11.0


# ---------------------------------------------------------------------------
# Scenario-level three-way differential.
# ---------------------------------------------------------------------------


class TestRaggedEngineEquivalence:
    def test_randomized_profile_zoo_both_machines(self):
        _assert_three_way(_ragged_set(seed=0, count=4), (MI300X, TPU_V5E))

    def test_all_topologies_group_sizes(self):
        machines = machine_grid()
        topos = {m.topology for m in machines}
        assert len(topos) == 2
        _assert_three_way(_ragged_set(seed=1, count=2), machines[:4])

    def test_rccl_and_dma_into_place(self):
        scenarios = _ragged_set(seed=2, count=2)
        _assert_three_way(scenarios, (MI300X,), dma=False)
        _assert_three_way(scenarios, (TPU_V5E,), dma_into_place=True)

    def test_indivisible_m_masked_and_raises(self):
        gemm = GemmShape(1001, 8192, 8192)
        sc = RaggedScenario("bad", "EP", "t", gemm, StepProfile.uniform(4))
        rb = RaggedBatch.from_ragged_scenarios([sc])
        grid = core_batch.evaluate_ragged_grid(rb, (MI300X,))
        for sched in FICCO:
            l = grid.schedule_idx(sched)
            assert not grid.valid[l, 0, 0]
            with pytest.raises(ValueError):
                simulate(gemm, MI300X, sched, profile=sc.profile)
        assert grid.valid[grid.schedule_idx(Schedule.SERIAL), 0, 0]

    def test_serial_and_p2p_ignore_profile(self):
        gemm = GemmShape(65536, 28672, 8192)
        for sched in (Schedule.SERIAL, Schedule.SHARD_P2P):
            a = simulate(gemm, MI300X, sched)
            b = simulate(
                gemm, MI300X, sched, profile=StepProfile.skewed(8, 4.0)
            )
            assert a.total == b.total and a.exposed_comm == b.exposed_comm


# ---------------------------------------------------------------------------
# Uniform path: bit-identity with the pre-PR engine.
# ---------------------------------------------------------------------------


class TestUniformPathUntouched:
    # Golden totals captured from the uniform engine at the PR-2 commit
    # (a92a83f), full float64 repr: (schedule_idx, scenario_idx in
    # TABLE_I, machine_idx in (MI300X, TPU_V5E)) -> total seconds.
    GOLDEN = {
        (0, 0, 0): 0.015746150880499563,
        (0, 5, 1): 0.051622680085611765,
        (1, 12, 0): 0.3924524961719757,
        (2, 0, 1): 0.04665035948169961,
        (2, 5, 0): 0.009574582152165011,
        (3, 12, 1): 0.5316026195958189,
        (4, 0, 0): 0.1172605248278478,
        (4, 12, 1): 0.5061417773647158,
        (5, 5, 0): 0.009650411517192505,
        (5, 12, 0): 0.23844371907157316,
    }

    def test_uniform_grid_bit_identical_to_pre_pr(self):
        sb = ScenarioBatch.from_scenarios(TABLE_I)
        grid = evaluate_grid(sb, (MI300X, TPU_V5E))
        for (l, i, j), want in self.GOLDEN.items():
            assert grid.total[l, i, j] == want, (l, i, j)

    def test_uniform_profile_reproduces_uniform_engine(self):
        """A 1/g x g profile through the ragged engine == the uniform
        engine, bit-for-bit (M divisible by g^2, K by g)."""
        scen = [
            s for s in TABLE_I
            if s.gemm.m % (16 * 16) == 0 and s.gemm.k % 16 == 0
        ]
        assert len(scen) >= 8
        for machine in (MI300X, TPU_V5E):
            g = machine.group
            rs = [
                RaggedScenario.from_scenario(s, StepProfile.uniform(g))
                for s in scen
            ]
            rg = core_batch.evaluate_ragged_grid(rs, (machine,))
            ug = evaluate_grid(
                ScenarioBatch.from_scenarios(scen), (machine,)
            )
            for sched in GRID_SCHEDULES:
                if sched is Schedule.UNIFORM_FUSED_2D:
                    # ragged 2D cuts K fractionally (no k%g validity bit)
                    continue
                l = ug.schedule_idx(sched)
                both = ug.valid[l, :, 0] & rg.valid[l, :, 0]
                assert (
                    rg.total[l, both, 0] == ug.total[l, both, 0]
                ).all(), sched
                assert (
                    rg.exposed[l, both, 0] == ug.exposed[l, both, 0]
                ).all(), sched

    def test_padding_invariance(self):
        """Zero-padding a profile never changes any engine figure."""
        gemm = GemmShape(131072, 28672, 8192)
        p = StepProfile.skewed(6, 3.0)
        for sched in FICCO:
            a = simulate(gemm, MI300X, sched, profile=p)
            b = simulate(gemm, MI300X, sched, profile=p.padded(11))
            assert a.total == b.total
            assert a.exposed_comm == b.exposed_comm
            assert a.comm_busy == b.comm_busy


# ---------------------------------------------------------------------------
# Step profiles.
# ---------------------------------------------------------------------------


class TestStepProfile:
    def test_validation(self):
        with pytest.raises(ValueError):
            StepProfile(())
        with pytest.raises(ValueError):
            StepProfile((0.5, 0.6))
        with pytest.raises(ValueError):
            StepProfile((-0.1, 1.1))
        with pytest.raises(ValueError):
            StepProfile.skewed(4, 0.0)

    def test_quantize_sums_and_determinism(self):
        for total in (7, 64, 1000, 12345):
            for p in _profiles():
                sizes = p.quantize(total)
                assert sum(sizes) == total
                assert len(sizes) == p.steps
                assert all(s >= 0 for s in sizes)
                assert sizes == p.quantize(total)  # deterministic

    def test_uniform_quantize_exact(self):
        assert StepProfile.uniform(8).quantize(64) == (8,) * 8

    def test_imbalance(self):
        assert StepProfile.uniform(8).imbalance == pytest.approx(1.0)
        assert StepProfile((1.0, 0.0)).imbalance == pytest.approx(1.0)
        assert StepProfile.skewed(8, 4.0).imbalance > 3.0
        # padding must not dilute imbalance (active steps only)
        p = StepProfile.skewed(4, 2.0)
        assert p.padded(9).imbalance == pytest.approx(p.imbalance)

    def test_padded_trimmed_roundtrip(self):
        p = StepProfile.zipf(5, 1.0)
        assert p.padded(9).trimmed() == p
        with pytest.raises(ValueError):
            p.padded(3)

    def test_digest_stable_and_uniform_short(self):
        assert StepProfile.uniform(16).digest() == "u16"
        a = StepProfile.skewed(8, 2.0).digest()
        assert a == StepProfile.skewed(8, 2.0).digest()
        assert a != StepProfile.skewed(8, 4.0).digest()

    def test_ragged_scenario_grid_families(self):
        fam = ragged_scenario_grid(skews=(1.0, 2.0, 4.0))
        assert len({s.profile.name for s in fam}) >= 5  # 3 skews+zipf+topk
        assert all(s.parallelism == "EP" for s in fam)
        skew_levels = {
            s.profile.name for s in fam if s.profile.name.startswith("skew")
        }
        assert len(skew_levels) >= 3


# ---------------------------------------------------------------------------
# explore_grid over the skewed EP family (acceptance criterion).
# ---------------------------------------------------------------------------


class TestExploreRaggedGrid:
    def test_skewed_ep_family_both_backends(self):
        from repro.core import explore_grid

        fam = ragged_scenario_grid(steps=8, skews=(1.0, 2.0, 4.0))
        machines = (MI300X, TPU_V5E)
        ex_np = explore_grid(fam, machines=machines, backend="numpy")
        ex_jx = explore_grid(fam, machines=machines, backend="jax")
        assert ex_np.exact.shape == (len(fam), len(machines))
        np.testing.assert_allclose(
            ex_np.grid.total, ex_jx.grid.total, rtol=RTOL, equal_nan=True
        )
        assert (ex_np.heuristic_idx == ex_jx.heuristic_idx).all()
        s = ex_np.summary()
        assert "within5%" in s

    def test_skew_aware_gate_consistent_scalar_vs_batch(self):
        from repro.core import select_schedule, select_schedule_batch

        fam = ragged_scenario_grid(steps=8, skews=(1.0, 4.0))
        rb = RaggedBatch.from_ragged_scenarios(fam)
        for machine in (MI300X, TPU_V5E):
            picks = select_schedule_batch(
                rb.m, rb.n, rb.k, rb.dtype_bytes, machine,
                imbalance=rb.imbalance,
            )
            for i, sc in enumerate(fam):
                dec = select_schedule(sc.gemm, machine, profile=sc.profile)
                assert GRID_SCHEDULES[int(picks[i])] is dec.schedule, sc.name


# ---------------------------------------------------------------------------
# Kernel layer: skew-aware chunked A2A dispatch.
# ---------------------------------------------------------------------------


class TestSkewAwareMoeKernel:
    def test_skewed_chunks_match_serial_reference(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from repro.overlap.moe import ficco_a2a_ffn, serial_a2a_ffn

        mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))
        e, c, d, f = 4, 12, 8, 16
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((e, c, d)), jnp.float32)
        w_up = jnp.asarray(rng.standard_normal((e, d, f)), jnp.float32)
        w_dn = jnp.asarray(rng.standard_normal((e, f, d)), jnp.float32)
        profile = StepProfile.from_weights([6, 3, 2, 1])

        def run(fn, **kw):
            wrapped = jax.shard_map(
                lambda a, b, c_: fn(a, b, c_, axis_name="ep", **kw),
                mesh=mesh,
                in_specs=(P(), P(), P()),
                out_specs=P(),
                check_vma=False,
            )
            return np.asarray(wrapped(x, w_up, w_dn))

        want = run(serial_a2a_ffn)
        got = run(ficco_a2a_ffn, profile=profile)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # explicit sizes incl. an empty chunk
        got2 = run(ficco_a2a_ffn, chunk_sizes=(5, 0, 4, 3))
        np.testing.assert_allclose(got2, want, rtol=2e-5, atol=2e-5)

    def test_chunk_sizes_validated(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from repro.overlap.moe import ficco_a2a_ffn

        mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))
        x = jnp.zeros((2, 8, 4), jnp.float32)
        w_up = jnp.zeros((2, 4, 8), jnp.float32)
        w_dn = jnp.zeros((2, 8, 4), jnp.float32)
        with pytest.raises(ValueError):
            jax.shard_map(
                lambda a, b, c_: ficco_a2a_ffn(
                    a, b, c_, axis_name="ep", chunk_sizes=(3, 3)
                ),
                mesh=mesh,
                in_specs=(P(), P(), P()),
                out_specs=P(),
                check_vma=False,
            )(x, w_up, w_dn)

    def test_skewed_chunk_sizes_helper(self):
        from repro.overlap.moe import skewed_chunk_sizes

        sizes = skewed_chunk_sizes(64, StepProfile.skewed(4, 2.0))
        assert sum(sizes) == 64 and len(sizes) == 4
        assert sizes[-1] > sizes[0]
