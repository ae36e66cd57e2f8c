"""Observability stack: tracer, metrics, timelines, decision provenance.

Three contracts under test:

* The tracer/metrics layer is schema-stable (exports load in Perfetto,
  snapshots validate) and strictly no-op when disabled.
* The schedule timeline is *exactly* what ``simulate()`` integrates —
  lane sums equal the SimResult busy times, and the inefficiency
  signature's splits close algebraically.
* Every :meth:`Autotuner.pick` tier (cache / analytic / measured /
  heuristic fallback) records provenance matching the tier that actually
  fired, and a recorded decision log replays offline to the same
  choices.
"""

import copy
import json
import math
import os
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

from repro.core.batch import evaluate_grid
from repro.core.inefficiency import loss_components
from repro.core.machine import MI300X, TPU_V5E, machine_for_group
from repro.core.schedule_types import STUDIED, Schedule
from repro.core.simulator import schedule_steps, simulate
from repro.core.workload import GemmShape, StepProfile
from repro.obs import audit as obs_audit
from repro.obs import metrics as obs_metrics
from repro.obs import sentinel as obs_sentinel
from repro.obs import signature as obs_signature
from repro.obs import timeline as obs_timeline
from repro.obs import trace as obs_trace

GEMM = GemmShape(16384, 16384, 32768, 2)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _script(name, *argv):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", name), *argv],
        capture_output=True, text=True, env=env, cwd=REPO,
    )


def _cli(*argv):
    return _script("trace.py", *argv)


class TestTracer:
    def test_disabled_is_shared_noop(self):
        assert not obs_trace.enabled()
        sp = obs_trace.span("x", "t")
        assert sp is obs_trace.NULL_SPAN
        with sp as s:
            s.set(anything="goes")  # must not raise, must not record
        assert obs_trace.get_tracer() is None

    def test_span_records_complete_event(self):
        tr = obs_trace.enable()
        with obs_trace.span("work", "cat", foo=1) as sp:
            sp.set(bar=2)
        obs_trace.disable()
        (ev,) = tr.events
        assert ev["name"] == "work" and ev["ph"] == "X"
        assert ev["dur"] >= 0
        assert ev["args"] == {"foo": 1, "bar": 2}
        assert obs_trace.validate_trace(tr.to_json()) == []

    def test_export_roundtrip(self, tmp_path):
        path = str(tmp_path / "t.json")
        tr = obs_trace.enable(path)
        with obs_trace.span("a"):
            pass
        obs_trace.instant("mark", note="here")
        obs_trace.counter("rate", 3.5)
        assert obs_trace.disable() == path
        with open(path) as f:
            obj = json.load(f)
        assert obs_trace.validate_trace(obj) == []
        assert {e["ph"] for e in obj["traceEvents"]} == {"X", "i", "C"}

    def test_validate_catches_violations(self):
        bad = {"traceEvents": [
            {"ph": "X", "ts": 0, "pid": 1, "tid": 0},        # no name
            {"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 0},  # no dur
            {"name": "y", "ph": "i", "ts": "zero", "pid": 1, "tid": 0},
        ]}
        errors = obs_trace.validate_trace(bad)
        assert len(errors) >= 3
        joined = "\n".join(errors)
        assert "name" in joined and "dur" in joined and "ts" in joined
        assert obs_trace.validate_trace([]) != []
        assert obs_trace.validate_trace({}) != []


def _profile(tmp_path, body):
    """Run ``body`` under a CPU ``jax.profiler`` session; returns the
    host plane's events as (line, name, start_ns, end_ns, stats)."""
    import glob

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((line.name, e.name, e.start_ns, e.end_ns,
                            dict(e.stats)) for e in line.events)
    return out


class TestProfilerSink:
    """With the profiler sink on, the spans the program opens land in the
    JAX profiler's trace, on the clock of the ops they launch."""

    def test_spans_reach_the_profiler_trace_beside_the_ops(self, tmp_path):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((128, 128))
        f(x).block_until_ready()

        def body():
            with obs_trace.span("serve/step", "serve", pos=3) as sp:
                with obs_trace.span("serve/dispatch", "serve"):
                    f(x).block_until_ready()
                sp.set(tokens=4)

        obs_trace.enable_profiler()
        try:
            events = _profile(tmp_path, body)
        finally:
            obs_trace.disable_profiler()
        spans = {name: (s, e, stats) for _, name, s, e, stats in events
                 if name.startswith("serve/")}
        assert set(spans) == {"serve/step", "serve/dispatch"}
        step, dispatch = spans["serve/step"], spans["serve/dispatch"]
        # args given at entry and set after it are both kept
        assert step[2]["pos"] == 3 and step[2]["tokens"] == 4
        assert step[0] <= dispatch[0] <= dispatch[1] <= step[1]
        ops = [(s, e) for _, _, s, e, stats in events if "hlo_op" in stats]
        assert ops, "the CPU trace holds the jitted call's ops"
        assert all(dispatch[0] <= s and e <= dispatch[1] for s, e in ops)

    def test_off_records_nothing_and_returns_the_shared_noop(self):
        obs_trace.enable_profiler()
        assert obs_trace.profiler_enabled()
        assert not obs_trace.enabled() and obs_trace.get_tracer() is None
        with obs_trace.span("x") as sp:
            assert sp is not obs_trace.NULL_SPAN
        obs_trace.disable_profiler()
        assert not obs_trace.profiler_enabled()
        assert obs_trace.span("x", "t", a=1) is obs_trace.NULL_SPAN
        assert obs_trace._TRACER is None

    def test_json_sink_output_is_unchanged_by_the_profiler_sink(self):
        def record(profiler: bool):
            if profiler:
                obs_trace.enable_profiler()
            tr = obs_trace.enable()
            with obs_trace.span("work", "cat", foo=1) as sp:
                sp.set(bar=2)
            obs_trace.instant("mark", note="here")
            obs_trace.counter("rate", 3.5)
            obs_trace.disable()
            assert obs_trace.profiler_enabled() == profiler
            obs_trace.disable_profiler()
            assert obs_trace._TRACER is None
            return [{k: v for k, v in e.items() if k not in ("ts", "dur")}
                    for e in tr.events]

        plain = record(False)
        assert record(True) == plain
        assert [e["name"] for e in plain] == ["work", "mark", "rate"]
        assert plain[0]["args"] == {"foo": 1, "bar": 2}

    def test_engine_step_phases_nest_in_the_step(self):
        import jax

        from repro.configs import get_config
        from repro.models.model import build_model
        from repro.serve.engine import DecodeEngine, Request

        cfg = get_config("smollm-360m").reduced()
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        eng = DecodeEngine(cfg, params, batch_size=2, cache_len=16)
        tr = obs_trace.enable()
        eng.run([Request(np.asarray([1, 2], np.int32), max_new_tokens=2)])
        obs_trace.disable()
        by = {}
        for e in tr.events:
            by.setdefault(e["name"], []).append(e)
        phases = ("serve/feed", "serve/dispatch", "serve/fetch",
                  "serve/emit")
        steps = by["serve/step"]
        assert len(by["serve/run"]) == 1 and len(steps) == 3
        assert [s["args"]["tokens"] for s in steps] == [0, 1, 1]
        for name in phases:
            assert len(by[name]) == len(steps)
        for i, st in enumerate(steps):
            t0, t1 = st["ts"], st["ts"] + st["dur"]
            inner = [by[name][i] for name in phases]
            assert all(t0 <= p["ts"] and p["ts"] + p["dur"] <= t1
                       for p in inner)
            assert all(a["ts"] + a["dur"] <= b["ts"]
                       for a, b in zip(inner, inner[1:]))


class TestMetrics:
    def test_counter_histogram_snapshot(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("a").inc()
        reg.counter("a").inc(4)
        for v in (1.0, 2.0, 3.0, 10.0):
            reg.histogram("h").observe(v)
        snap = reg.snapshot()
        assert snap["counters"]["a"] == 5
        h = snap["histograms"]["h"]
        assert h["count"] == 4 and h["max"] == 10.0
        assert h["p50"] == 2.0
        assert obs_metrics.validate_snapshot(snap) == []

    def test_export_jsonl_appends(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        reg = obs_metrics.MetricsRegistry()
        reg.counter("c").inc()
        reg.export_jsonl(path)
        reg.counter("c").inc()
        reg.export_jsonl(path)
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert [ln["counters"]["c"] for ln in lines] == [1, 2]
        for ln in lines:
            assert obs_metrics.validate_snapshot(ln) == []

    def test_validate_snapshot_catches_violations(self):
        assert obs_metrics.validate_snapshot([]) != []
        assert obs_metrics.validate_snapshot({"counters": {}}) != []
        bad = {"ts": 0.0, "counters": {"x": "NaN-ish"}, "histograms": {}}
        assert obs_metrics.validate_snapshot(bad) != []

    def test_gate_agreement_rate(self):
        grid = evaluate_grid(
            [GemmShape(65536, 8192, 8192), GemmShape(512, 512, 512)],
            (MI300X,),
        )
        reg = obs_metrics.MetricsRegistry()
        rate = obs_metrics.observe_gate_agreement(grid, registry=reg)
        assert 0.0 <= rate <= 1.0
        snap = reg.snapshot()
        assert snap["counters"]["gate/points"] == 2
        assert snap["counters"]["gate/agree"] == round(rate * 2)


class TestHistogramReservoir:
    """The bounded-growth + thread-safety contract of Histogram."""

    def test_exact_below_reservoir_size(self):
        h = obs_metrics.Histogram()
        vals = [float(i) for i in range(1000)]
        for v in vals:
            h.observe(v)
        assert h.count == 1000
        assert h.total == sum(vals)
        assert sorted(h.values) == vals  # nothing sampled away yet
        assert h.percentile(0.5) == 499.0  # nearest-rank, exact

    def test_bounded_above_reservoir_size(self):
        r = obs_metrics.RESERVOIR_SIZE
        h = obs_metrics.Histogram(seed=1)
        n = 3 * r
        for i in range(n):
            h.observe(i / n)  # uniform on [0, 1)
        # count/sum/min/max exact, memory bounded.
        assert h.count == n
        assert len(h.values) == r
        assert abs(h.total - sum(i / n for i in range(n))) < 1e-6
        j = h.to_json()
        assert j["count"] == n
        assert j["min"] == 0.0 and j["max"] == (n - 1) / n
        # Percentiles carry the documented ~1/sqrt(K) sampling error;
        # 0.05 is ~6 sigma for K=4096 — loose enough to never flake,
        # tight enough to catch a broken reservoir (e.g. keeping only
        # the newest samples would push p50 toward the tail).
        assert abs(j["p50"] - 0.5) < 0.05
        assert abs(j["p95"] - 0.95) < 0.05

    def test_concurrent_observe_loses_nothing(self):
        import threading

        h = obs_metrics.Histogram()
        c = obs_metrics.Counter()
        n_threads, iters = 8, 20_000

        def worker():
            for _ in range(iters):
                c.inc()
                h.observe(1.0)

        threads = [
            threading.Thread(target=worker) for _ in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * iters
        assert c.value == total          # the bare += would lose counts
        assert h.count == total
        assert h.total == float(total)   # every observation summed
        assert len(h.values) == obs_metrics.RESERVOIR_SIZE

    def test_empty_histogram_snapshot(self):
        h = obs_metrics.Histogram()
        assert h.percentile(0.5) == 0.0
        j = h.to_json()
        assert j == {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                     "p50": 0.0, "p95": 0.0}


class TestTimeline:
    @pytest.mark.parametrize("schedule", list(STUDIED))
    def test_lanes_integrate_to_simulate(self, schedule):
        steps = schedule_steps(GEMM, TPU_V5E, schedule, dma=True)
        res = simulate(GEMM, TPU_V5E, schedule, dma=True)
        lanes = obs_timeline.lane_intervals(steps)
        assert math.isclose(
            sum(d for _, d in lanes["comm"]), res.comm_busy, rel_tol=1e-12
        )
        assert math.isclose(
            sum(d for _, d in lanes["compute"]), res.compute_busy,
            rel_tol=1e-12,
        )
        assert math.isclose(
            sum(d for _, d in lanes["exposed"]) + 0.0, res.exposed_comm,
            rel_tol=1e-9, abs_tol=1e-15,
        )

    def test_signature_splits_close(self):
        steps = schedule_steps(
            GEMM, TPU_V5E, Schedule.UNIFORM_FUSED_1D, dma=True
        )
        sig = obs_timeline.inefficiency_signature(steps)
        res = steps.run()
        # contention + decomposition = total comm overhead over serial
        assert math.isclose(
            sig["comm_contention_s"] + sig["comm_decomposition_s"],
            res.comm_busy - res.serial_comm, rel_tol=1e-12, abs_tol=1e-15,
        )
        assert math.isclose(
            sig["gemm_contention_s"] + sig["gemm_decomposition_s"],
            res.compute_busy - res.serial_gemm, rel_tol=1e-12,
            abs_tol=1e-15,
        )
        assert sig["speedup"] == res.speedup
        assert sig["exposure_s"] == res.exposed_comm

    def test_ragged_signature_omits_cil_split(self):
        profile = StepProfile.from_weights((0.5, 0.3, 0.1, 0.1))
        steps = schedule_steps(
            GEMM, TPU_V5E, Schedule.HETERO_FUSED_1D, dma=True,
            profile=profile,
        )
        sig = obs_timeline.inefficiency_signature(steps)
        assert "comm_contention_s" not in sig
        assert sig["total_s"] == steps.run().total

    def test_schedule_timeline_exports_valid_trace(self):
        tr, sig = obs_timeline.schedule_timeline(
            GEMM, TPU_V5E, Schedule.UNIFORM_FUSED_1D
        )
        obj = tr.to_json()
        assert obs_trace.validate_trace(obj) == []
        names = {e["name"] for e in obj["traceEvents"]}
        assert {"a2a_chunk", "gemm_step", "inefficiency_signature"} <= names
        assert sig["schedule"] == "uniform-fused-1d"

    def test_grid_timeline_defaults_to_best(self):
        grid = evaluate_grid([GEMM], (TPU_V5E,))
        tr, sig = obs_timeline.grid_timeline(grid, 0)
        best = grid.schedules[int(grid.best_idx()[0, 0])]
        assert sig["schedule"] == best.value
        assert obs_trace.validate_trace(tr.to_json()) == []


@pytest.mark.autotune
class TestTunerProvenance:
    """One test per tier: the recorded provenance must match the tier
    that actually fired."""

    def _tuner(self, tmp_path, **kw):
        from repro.autotune import Autotuner

        log = obs_audit.AuditLog(str(tmp_path / "decisions.jsonl"))
        return Autotuner(backend="numpy", audit=log, **kw), log

    def _records(self, log):
        return obs_audit.read_audit(log.path)

    def test_analytic_tier(self, tmp_path):
        t, log = self._tuner(tmp_path)
        tr = obs_trace.enable()
        dec = t.pick(GEMM, TPU_V5E, group=8)
        obs_trace.disable()
        assert dec.source == "analytic"
        assert dec.key and dec.shortlist
        (rec,) = self._records(log)
        assert rec["source"] == "analytic"
        assert rec["schedule"] == dec.schedule.value
        assert rec["key"] == dec.key
        spans = [e for e in tr.events if e["name"] == "tuner/pick"]
        assert spans and spans[0]["args"]["tier"] == "analytic"
        assert spans[0]["args"]["cache"] == "miss"
        rates = obs_metrics.tuner_tier_rates()
        assert rates["analytic"] == 1.0
        assert rates.get("cache", 0.0) == 0.0

    def test_cache_tier(self, tmp_path):
        t, log = self._tuner(tmp_path)
        first = t.pick(GEMM, TPU_V5E, group=8)
        tr = obs_trace.enable()
        dec = t.pick(GEMM, TPU_V5E, group=8)
        obs_trace.disable()
        assert dec.source == "cache"
        assert dec.schedule is first.schedule
        recs = self._records(log)
        assert [r["source"] for r in recs] == ["analytic", "cache"]
        spans = [e for e in tr.events if e["name"] == "tuner/pick"]
        assert spans[0]["args"]["cache"] == "hit"
        rates = obs_metrics.tuner_tier_rates()
        assert rates["analytic"] == 0.5 and rates["cache"] == 0.5

    def test_measured_tier(self, tmp_path):
        import jax
        import jax.numpy as jnp

        mesh = jax.make_mesh((1,), ("tp",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
        t, log = self._tuner(tmp_path)
        dec = t.measure(
            x, w, mesh=mesh, axis_name="tp", machine=TPU_V5E,
            schedules=[Schedule.SERIAL], iters=1,
        )
        assert dec.source == "measured"
        (rec,) = self._records(log)
        assert rec["kind"] == "measure" and rec["source"] == "measured"
        assert rec["measured_total_s"] > 0
        assert rec["schedule"] == dec.schedule.value

    def test_heuristic_tier_malformed_gate(self, tmp_path, monkeypatch):
        """A broken analytic backend plus a malformed learned gate must
        degrade to the scalar-gated tree — recorded as such."""

        class BrokenGate:
            def __call__(self, *a, **k):
                raise RuntimeError("malformed artifact")

        t, log = self._tuner(tmp_path, gate=BrokenGate())
        monkeypatch.setattr(
            type(t), "_shortlist",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("backend")),
        )
        dec = t.pick(GEMM, TPU_V5E, group=8)
        assert dec.source == "heuristic"
        assert dec.gate is not None and dec.gate["kind"] is None
        (rec,) = self._records(log)
        assert rec["source"] == "heuristic"
        assert rec["gate"]["kind"] is None
        # heuristic decisions are never persisted
        assert t.cache.get(dec.key) is None


@pytest.mark.autotune
class TestAuditReplay:
    def test_replay_reproduces_every_pick(self, tmp_path):
        from repro.autotune import Autotuner

        log = obs_audit.AuditLog(str(tmp_path / "decisions.jsonl"))
        t = Autotuner(backend="numpy", audit=log)
        t.pick(GEMM, TPU_V5E, group=8)
        t.pick(GemmShape(512, 512, 512, 2), MI300X)
        t.pick(GEMM, TPU_V5E, group=8)  # cache hit
        records = obs_audit.read_audit(log.path)
        assert obs_audit.validate_audit(records) == []
        res = obs_audit.replay(records)
        assert res.ok
        assert res.replayed == 3 and res.matched == 3
        assert res.mismatches == []

    def test_replay_flags_tampered_log(self, tmp_path):
        from repro.autotune import Autotuner

        log = obs_audit.AuditLog(str(tmp_path / "decisions.jsonl"))
        Autotuner(backend="numpy", audit=log).pick(GEMM, TPU_V5E, group=8)
        records = obs_audit.read_audit(log.path)
        wrong = (
            Schedule.SERIAL.value
            if records[0]["schedule"] != Schedule.SERIAL.value
            else Schedule.UNIFORM_FUSED_1D.value
        )
        records[0]["schedule"] = wrong
        res = obs_audit.replay(records)
        assert not res.ok and res.mismatches

    def test_read_audit_raises_on_malformed(self, tmp_path):
        path = str(tmp_path / "bad.jsonl")
        with open(path, "w") as f:
            f.write('{"kind": "pick"}\n{oops\n')
        with pytest.raises(ValueError):
            obs_audit.read_audit(path)


class TestSweepInstrumentation:
    def test_sweep_grid_emits_spans_and_counters(self):
        from repro.sweep import sweep_grid, synthetic_batch

        sb = synthetic_batch(16, seed=0)
        machines = (TPU_V5E,)
        tr = obs_trace.enable()
        res = sweep_grid(sb, machines, backend="numpy", num_shards=3)
        obs_trace.disable()
        names = [e["name"] for e in tr.events]
        assert names.count("sweep/dispatch") == 3
        assert names.count("sweep/compute") == 3
        assert names.count("sweep/reduce") == 3
        assert names.count("sweep/run") == 1
        snap = obs_metrics.get_metrics().snapshot()
        assert snap["counters"]["sweep/shards"] == 3
        assert snap["counters"]["sweep/scenarios"] == 16
        assert snap["histograms"]["sweep/shard_seconds"]["count"] == 3
        assert len(res.summaries) == 3

    def test_merge_sweep_host_throughput_skew(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            from merge_sweep import merge_streams
        finally:
            sys.path.pop(0)
        from repro.sweep import ShardSummary

        def host(idx, shards, wall, n):
            return (
                [
                    ShardSummary(s, s * 8, s * 8 + 8, 8, 8, 0.1, 80.0,
                                 {}, 0.5, 1.2)
                    for s in shards
                ],
                [{
                    "host_index": idx, "wall_seconds": wall,
                    "n_scenarios": n, "owned_shards": list(shards),
                    "plan_shards": 4,
                }],
            )

        merged = merge_streams([host(0, (0, 1), 2.0, 16),
                                host(1, (2, 3), 8.0, 16)])
        assert merged["complete"]
        assert merged["host_throughput"] == {"0": 8.0, "1": 2.0}
        assert merged["host_throughput_skew"] == 4.0
        solo = merge_streams([host(0, (0, 1, 2, 3), 2.0, 32)])
        assert solo["host_throughput_skew"] is None


class TestCLI:
    def test_timeline_subcommand(self, tmp_path):
        out = str(tmp_path / "tl.json")
        r = _cli(
            "timeline", "--scenario", "g1", "--schedule",
            "uniform-fused-1d", "--out", out,
        )
        assert r.returncode == 0, r.stderr
        with open(out) as f:
            obj = json.load(f)
        assert obs_trace.validate_trace(obj) == []
        assert any(
            e["name"] == "inefficiency_signature"
            for e in obj["traceEvents"]
        )
        r2 = _cli("validate", out)
        assert r2.returncode == 0, r2.stderr

    def test_validate_rejects_garbage(self, tmp_path):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"traceEvents": [{"ph": "X"}]}, f)
        assert _cli("validate", bad).returncode == 1

    def test_metrics_subcommand(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        reg = obs_metrics.MetricsRegistry()
        reg.counter("tuner/decisions").inc(4)
        reg.counter("tuner/pick.cache").inc(3)
        reg.counter("tuner/pick.analytic").inc()
        reg.export_jsonl(path)
        r = _cli("metrics", path)
        assert r.returncode == 0, r.stderr
        assert "tier rates" in r.stdout
        assert "cache=75.00%" in r.stdout


class TestEnvHooks:
    @pytest.mark.slow
    def test_repro_trace_env_exports_at_exit(self, tmp_path):
        path = str(tmp_path / "env.trace.json")
        env = dict(
            os.environ,
            PYTHONPATH=os.path.join(REPO, "src"),
            REPRO_TRACE=path,
        )
        code = (
            "from repro.core.simulator import simulate\n"
            "from repro.obs import trace\n"
            "assert trace.enabled()\n"
            "with trace.span('x'):\n"
            "    pass\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env,
        )
        assert r.returncode == 0, r.stderr
        with open(path) as f:
            assert obs_trace.validate_trace(json.load(f)) == []


class TestLossComponents:
    """The streaming attribution's algebra: components sum back to the
    analytic schedule time, exactly."""

    @pytest.mark.parametrize("schedule", list(STUDIED))
    def test_uniform_lowerings_integrate_exactly(self, schedule):
        steps = schedule_steps(GEMM, TPU_V5E, schedule, dma=True)
        res = steps.run()
        comps = loss_components(
            res, comm_cil=steps.comm_cil, gemm_cil=steps.gemm_cil
        )
        expected = (
            obs_signature.RAGGED_COMPONENTS if steps.gemm_cil is None
            else obs_signature.UNIFORM_COMPONENTS
        )
        assert set(comps) == set(expected)
        assert math.isclose(
            sum(comps.values()), res.total, rel_tol=1e-12
        )

    def test_ragged_lowering_integrates_exactly(self):
        profile = StepProfile.from_weights((0.5, 0.25, 0.15, 0.1))
        steps = schedule_steps(
            GEMM, TPU_V5E, Schedule.HETERO_FUSED_1D, dma=True,
            profile=profile,
        )
        res = steps.run()
        comps = loss_components(res)
        assert set(comps) == set(obs_signature.RAGGED_COMPONENTS)
        assert math.isclose(
            sum(comps.values()), res.total, rel_tol=1e-12
        )

    def test_pinned_busies_close_algebraically(self):
        """Hand-pinned busy times: every split term is exactly the
        documented formula, and both variants sum to the pinned total."""
        res = types.SimpleNamespace(
            total=10.0, compute_busy=6.0, exposed_comm=1.5,
            serial_gemm=4.0,
        )
        comps = loss_components(res, comm_cil=1.2, gemm_cil=1.5)
        assert comps["serial_gemm_s"] == 4.0
        assert comps["gemm_decomposition_s"] == 6.0 / 1.5 - 4.0
        assert comps["gemm_contention_s"] == 6.0 * (1.0 - 1.0 / 1.5)
        assert comps["exposed_comm_s"] == 1.5
        assert comps["comm_tail_s"] == 10.0 - 6.0 - 1.5
        assert math.isclose(sum(comps.values()), 10.0, rel_tol=1e-12)
        ragged = loss_components(res)
        assert set(ragged) == set(obs_signature.RAGGED_COMPONENTS)
        assert math.isclose(sum(ragged.values()), 10.0, rel_tol=1e-12)


class TestSignature:
    SCHED = Schedule.UNIFORM_FUSED_1D

    @pytest.mark.parametrize("schedule", list(STUDIED))
    def test_decision_signature_integrates(self, schedule):
        sig = obs_signature.decision_signature(
            GEMM, TPU_V5E, schedule, group=8
        )
        expected = (
            obs_signature.RAGGED_COMPONENTS if sig["ragged"]
            else obs_signature.UNIFORM_COMPONENTS
        )
        assert set(sig["components"]) == set(expected)
        assert math.isclose(
            sum(sig["components"].values()), sig["total_s"],
            rel_tol=1e-12,
        )
        assert sig["schedule"] == schedule.value

    def test_family_and_scenario_class(self):
        assert obs_signature.machine_family("tpu_v5e/dma") == "tpu_v5e"
        flops = 2.0 * GEMM.m * GEMM.n * GEMM.k
        assert obs_signature.scenario_class(GEMM) == (
            f"uniform/f{int(math.log2(flops))}"
        )
        prof = StepProfile.from_weights((0.6, 0.4), name="skew")
        assert obs_signature.scenario_class(GEMM, prof).startswith("skew/")

    def test_stream_memoizes_and_defers_flush(self):
        stream = obs_signature.SignatureStream()
        for _ in range(5):
            stream.observe_decision(
                GEMM, TPU_V5E, self.SCHED, group=8, source="analytic"
            )
        assert stream.errors == 0
        assert stream.observed == 0      # deferred: nothing folded yet
        assert len(stream.acc) == 0
        snap = stream.snapshot()         # flush + read
        assert stream.observed == 5
        (cell,) = snap["cells"]
        assert cell["count"] == 5
        assert cell["sources"] == {"analytic": 5}
        sig = obs_signature.decision_signature(
            GEMM, TPU_V5E, self.SCHED, group=8
        )
        assert math.isclose(
            cell["total_s"]["sum"], 5 * sig["total_s"], rel_tol=1e-12
        )
        # The deferred fold preserves the integration identity.
        comp_sum = sum(s["sum"] for s in cell["components"].values())
        assert math.isclose(
            comp_sum, cell["total_s"]["sum"], rel_tol=1e-12
        )
        assert obs_signature.validate_signature(snap) == []

    def test_measured_residual_accumulates(self):
        stream = obs_signature.SignatureStream()
        sig = obs_signature.decision_signature(
            GEMM, TPU_V5E, self.SCHED, group=8
        )
        model = sig["total_s"]
        for _ in range(3):
            stream.observe_decision(
                GEMM, TPU_V5E, self.SCHED, group=8, source="measured",
                model_total_s=model, measured_total_s=model * 1.25,
            )
        (cell,) = stream.snapshot()["cells"]
        assert cell["residual"]["count"] == 3
        assert math.isclose(
            cell["residual"]["mean"], math.log(1.25), rel_tol=1e-12
        )
        assert cell["sources"] == {"measured": 3}

    def test_roll_starts_fresh_window(self):
        stream = obs_signature.SignatureStream()
        stream.observe_decision(GEMM, TPU_V5E, self.SCHED, group=8)
        first = stream.roll()
        assert len(first["cells"]) == 1
        assert stream.snapshot()["cells"] == []
        # The memo survives a roll; new observations land in the new
        # window without re-lowering.
        stream.observe_decision(GEMM, TPU_V5E, self.SCHED, group=8)
        assert len(stream.snapshot()["cells"]) == 1

    def test_unlowerable_decision_remembered_not_raised(self):
        bad = GemmShape(4, 4, 4, 2)  # M=4 not divisible 8 ways
        with pytest.raises(ValueError):
            obs_signature.decision_signature(
                bad, TPU_V5E, self.SCHED, group=8
            )
        stream = obs_signature.SignatureStream()
        for _ in range(3):
            stream.observe_decision(bad, TPU_V5E, self.SCHED, group=8)
        assert stream.errors == 1  # lowered once, miss remembered
        assert stream.snapshot()["cells"] == []

    def test_accumulator_bounds_cells(self):
        acc = obs_signature.SignatureAccumulator(max_cells=2)
        for i in range(3):
            acc.observe(
                "fam", f"s{i}", "serial", {"compute_busy_s": 1.0}, 1.0,
                ragged=True,
            )
        assert len(acc) == 2
        assert acc.evicted == 1

    def test_validate_signature_catches_violations(self):
        assert obs_signature.validate_signature([]) != []
        assert obs_signature.validate_signature({"ts": 0.0}) != []
        stream = obs_signature.SignatureStream()
        stream.observe_decision(GEMM, TPU_V5E, self.SCHED, group=8)
        snap = stream.snapshot()
        del snap["cells"][0]["components"]["exposed_comm_s"]
        errs = obs_signature.validate_signature(snap)
        assert any("exposed_comm_s" in e for e in errs)

    def test_overlay_grid(self):
        stream = obs_signature.SignatureStream()
        for sched in (Schedule.SERIAL, self.SCHED):
            for _ in range(2):
                stream.observe_decision(GEMM, TPU_V5E, sched, group=8)
        grid = obs_signature.overlay([stream.snapshot()])
        key = (
            obs_signature.machine_family(TPU_V5E.name),
            obs_signature.scenario_class(GEMM),
        )
        assert key in grid
        row = grid[key]
        assert set(row) == {"serial", self.SCHED.value}
        for agg in row.values():
            assert agg["count"] == 2
            assert agg["mean_total_s"] > 0.0
            # Dominant is a LOSS category, never the work itself.
            assert agg["dominant"] in obs_signature.UNIFORM_COMPONENTS
            assert agg["dominant"] != "serial_gemm_s"
            for f in agg["loss_fractions"].values():
                assert -1e-9 <= f <= 1.0
        # Fully serial: the entire loss is exposed communication.
        assert row["serial"]["dominant"] == "exposed_comm_s"

    def test_enable_disable_roundtrip(self, tmp_path):
        path = str(tmp_path / "sig.jsonl")
        stream = obs_signature.enable_signatures(path)
        assert obs_signature.get_signatures() is stream
        stream.observe_decision(GEMM, TPU_V5E, self.SCHED, group=8)
        snap = obs_signature.disable_signatures()
        assert obs_signature.get_signatures() is None
        assert len(snap["cells"]) == 1
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert len(lines) == 1
        assert obs_signature.validate_signature(lines[0]) == []

    def test_committed_overhead_within_serve_budget(self):
        """The ISSUE's acceptance bound, deterministically: the
        committed per-observe signature cost is <= 5% of the committed
        per-decision serve cost (both us_per_call in BENCH_sweep.json,
        both gated against regression by benchmarks/run.py)."""
        with open(os.path.join(REPO, "BENCH_sweep.json")) as f:
            bench = json.load(f)
        assert "obs/signature_overhead" in bench
        assert "obs/sentinel_step" in bench
        from benchmarks.run import THROUGHPUT_KEYS

        assert "obs/signature_overhead" in THROUGHPUT_KEYS
        assert "obs/sentinel_step" in THROUGHPUT_KEYS
        assert bench["obs/signature_overhead"] <= (
            0.05 * bench["serve/decisions_per_s"]
        )


@pytest.mark.autotune
class TestSignatureTunerFeed:
    def test_autotuner_pick_feeds_stream_per_tier(self, tmp_path):
        from repro.autotune import Autotuner

        stream = obs_signature.enable_signatures(None)
        t = Autotuner(backend="numpy")
        dec = t.pick(GEMM, TPU_V5E, group=8)
        t.pick(GEMM, TPU_V5E, group=8)  # cache tier feeds too
        snap = stream.snapshot()
        (cell,) = [
            c for c in snap["cells"]
            if c["schedule"] == dec.schedule.value
        ]
        assert cell["sources"].get("analytic") == 1
        assert cell["sources"].get("cache") == 1
        assert cell["count"] == 2
        assert math.isclose(
            sum(s["sum"] for s in cell["components"].values()),
            cell["total_s"]["sum"], rel_tol=1e-12,
        )


class TestSentinel:
    def _sentinel(self, **kw):
        kw.setdefault("min_samples", 4)
        return obs_sentinel.Sentinel(obs_sentinel.SentinelConfig(**kw))

    def test_biased_residuals_trip_and_latch(self):
        s = self._sentinel()
        fired = [
            s.observe_residual(1.0e-3, 2.0e-3, key="k") for _ in range(12)
        ]
        assert any(fired)
        assert s.should_refit()
        assert s.alarms == 1  # latched: exactly one alarm for the episode
        (ev,) = [e for e in s.events if e["kind"] == "sentinel_alarm"]
        assert ev["channel"] == "residual"
        assert ev["n"] >= 4
        assert ev["ewma"] > 0.0  # measured slower than predicted

    def test_unbiased_residuals_stay_quiet(self):
        s = self._sentinel()
        for i in range(200):
            measured = 1.0e-3 * math.exp(0.05 if i % 2 else -0.05)
            s.observe_residual(1.0e-3, measured)
        assert not s.should_refit()
        assert s.alarms == 0

    def test_agreement_channel_alarms_below_floor(self):
        s = self._sentinel()
        assert not s.observe_agreement(0.9)
        fired = [s.observe_agreement(0.1) for _ in range(6)]
        assert any(fired)
        (ev,) = [e for e in s.events if e["kind"] == "sentinel_alarm"]
        assert ev["channel"] == "agreement"

    def test_refit_resets_and_recovery_summarizes(self):
        s = self._sentinel()
        for _ in range(8):
            s.observe_residual(1.0e-3, 2.0e-3)
        assert s.should_refit()
        ev = s.record_refit(
            {"fit_sigma": 0.2, "shortlist": [1, 2]}, trigger="drift"
        )
        assert ev["kind"] == "sentinel_refit"
        assert ev["trigger"] == "drift"
        assert ev["channel"] == "residual"
        assert ev["report"]["fit_sigma"] == 0.2
        assert "shortlist" not in ev["report"]  # non-scalars dropped
        assert not s.should_refit()  # latch cleared
        assert s.state()["cusum_pos"] == 0.0
        for _ in range(4):  # healthy post-refit residuals
            s.observe_residual(1.0e-3, 1.0e-3)
        (rec,) = [
            e for e in s.events if e["kind"] == "sentinel_recovery"
        ]
        assert rec["samples"] == 4
        assert rec["post_mean"] == 0.0
        assert abs(rec["pre_refit_ewma"]) > 0.1  # the drift it recovered from
        assert not s.state()["recovering"]

    def test_degenerate_inputs_skipped(self):
        s = self._sentinel()
        assert not s.observe_residual(0.0, 1.0)
        assert not s.observe_residual(1.0, -1.0)
        assert not s.observe_residual("x", 1.0)
        assert not s.observe_agreement(1.5)
        assert s.state()["n"] == 0

    def test_on_alarm_hook_fires_once_per_episode(self):
        s = self._sentinel()
        kicks = []
        s.on_alarm = lambda: kicks.append(1)
        for _ in range(12):
            s.observe_residual(1.0e-3, 3.0e-3)
        assert kicks == [1]

    def test_validate_export_and_cli(self, tmp_path):
        s = self._sentinel()
        for _ in range(8):
            s.observe_residual(1.0e-3, 2.0e-3)
        s.record_refit({}, trigger="drift")
        for _ in range(4):
            s.observe_residual(1.0e-3, 1.0e-3)
        assert obs_sentinel.validate_sentinel(s.events) == []
        path = str(tmp_path / "sentinel.jsonl")
        n = s.export_jsonl(path)
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert len(lines) == n == len(s.events)
        kinds = [ln["kind"] for ln in lines]
        assert kinds[0] == "sentinel_alarm"
        assert "sentinel_refit" in kinds and "sentinel_recovery" in kinds
        r = _cli("validate", "--kind", "sentinel", path)
        assert r.returncode == 0, r.stderr

    def test_validate_catches_violations(self):
        errs = obs_sentinel.validate_sentinel([
            {"kind": "nope"},
            {"kind": "sentinel_alarm", "channel": "psychic"},
            "not-an-object",
            {"kind": "sentinel_refit", "ts": 0.0, "n": 0,
             "cusum_pos": 0.0, "cusum_neg": 0.0, "sigma": 0.1},
        ])
        assert len(errs) >= 4
        assert any("trigger" in e for e in errs)


class TestAuditRotation:
    def _fill(self, log, n):
        for i in range(n):
            log.record({
                "kind": "pick", "schedule": "serial",
                "source": "analytic", "machine": "tpu-v5e-axis16",
                "group": 8, "m": 64 + i, "n": 64, "k": 64,
                "dtype_bytes": 2, "key": f"k{i}",
            })

    def test_rotation_bounds_disk_keeps_newest(self, tmp_path):
        path = str(tmp_path / "audit.jsonl")
        log = obs_audit.AuditLog(path, max_bytes=600, keep=2)
        self._fill(log, 40)
        assert log.rotations > 2
        assert os.path.exists(path + ".1")
        assert os.path.exists(path + ".2")
        assert not os.path.exists(path + ".3")  # keep bound enforced
        assert obs_audit.audit_segments(path) == [
            path + ".2", path + ".1", path
        ]
        recs = obs_audit.read_audit_segments(path)
        assert obs_audit.validate_audit(recs) == []
        # Oldest-beyond-keep dropped; what remains is the NEWEST
        # contiguous run, in append order across segments.
        ms = [r["m"] for r in recs]
        assert 0 < len(ms) < 40
        assert ms == list(range(64 + 40 - len(ms), 64 + 40))

    def test_unbounded_by_default_never_rotates(self, tmp_path):
        path = str(tmp_path / "audit.jsonl")
        log = obs_audit.AuditLog(path)  # env unset under conftest
        assert log.max_bytes == 0
        self._fill(log, 20)
        assert log.rotations == 0
        assert obs_audit.audit_segments(path) == [path]
        assert len(obs_audit.read_audit_segments(path)) == 20

    def test_env_defaults(self, tmp_path, monkeypatch):
        monkeypatch.setenv(obs_audit.ENV_MAX_BYTES, "123")
        monkeypatch.setenv(obs_audit.ENV_KEEP, "5")
        log = obs_audit.AuditLog(str(tmp_path / "a.jsonl"))
        assert log.max_bytes == 123 and log.keep == 5
        # Explicit args beat the environment.
        log2 = obs_audit.AuditLog(
            str(tmp_path / "b.jsonl"), max_bytes=0, keep=1
        )
        assert log2.max_bytes == 0 and log2.keep == 1

    def test_aux_kinds_share_the_stream(self):
        recs = [
            {"kind": "adapt_measure", "ts": 1.0},
            {"kind": "sentinel_alarm", "ts": 2.0, "channel": "residual"},
            {"kind": "sentinel_refit", "ts": 3.0, "trigger": "drift"},
        ]
        assert obs_audit.validate_audit(recs) == []
        assert obs_audit.validate_audit([{"kind": "adapt_measure"}]) != []
        res = obs_audit.replay(recs)
        assert res.total == 3 and res.replayed == 0
        assert len(res.skipped) == 3


@pytest.mark.autotune
class TestAuditRotatedReplay:
    def test_replay_spans_segments(self, tmp_path):
        from repro.autotune import Autotuner

        path = str(tmp_path / "decisions.jsonl")
        log = obs_audit.AuditLog(path, max_bytes=300, keep=4)
        t = Autotuner(backend="numpy", audit=log)
        t.pick(GEMM, TPU_V5E, group=8)
        t.pick(GemmShape(512, 512, 512, 2), MI300X)
        t.pick(GEMM, TPU_V5E, group=8)  # cache hit
        assert log.rotations >= 1  # the log actually rolled mid-run
        res = obs_audit.replay(path)  # path form walks all segments
        assert res.ok
        assert res.replayed == 3 and res.matched == 3


class TestSnapshotAtomicity:
    def test_tier_counters_never_tear_under_writer(self):
        """Regression: snapshot() must hold one lock across the whole
        read.  The writer bumps tuner/decisions BEFORE tuner/pick.*, so
        any snapshot where sum(pick.*) exceeds decisions observed a torn
        cut (the bug that made tuner_tier_rates deltas go negative)."""
        reg = obs_metrics.MetricsRegistry()
        stop = threading.Event()

        def writer():
            decisions = reg.counter("tuner/decisions")
            pick = reg.counter("tuner/pick.cache")
            while not stop.is_set():
                decisions.inc()
                pick.inc()

        t = threading.Thread(target=writer)
        t.start()
        try:
            prev = -1
            for _ in range(500):
                c = reg.snapshot()["counters"]
                picks = sum(
                    v for k, v in c.items()
                    if k.startswith("tuner/pick.")
                )
                decisions = c.get("tuner/decisions", 0)
                assert picks <= decisions
                assert decisions >= prev  # snapshots are monotone too
                prev = decisions
        finally:
            stop.set()
            t.join()
        assert prev > 0  # the writer actually ran against the reads


class TestFleetMerge:
    def _host_snap(self, idx, values, shards=10):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("sweep/shards").inc(shards)
        h = reg.histogram("sweep/shard_seconds")
        for v in values:
            h.observe(v)
        return reg.snapshot(
            reservoir=True,
            host={
                "hostname": f"host{idx}", "pid": 100 + idx,
                "host_index": idx,
            },
        )

    def test_counters_bit_exact_percentiles_from_union(self):
        a = self._host_snap(0, [1.0, 2.0, 3.0], shards=7)
        b = self._host_snap(1, [10.0, 20.0, 30.0], shards=5)
        m = obs_metrics.merge_snapshots([a, b])
        assert obs_metrics.validate_merged_snapshot(m) == []
        assert m["hosts"] == 2
        assert m["counters"]["sweep/shards"] == 12
        h = m["histograms"]["sweep/shard_seconds"]
        assert h["count"] == 6
        assert h["sum"] == 66.0
        assert h["min"] == 1.0 and h["max"] == 30.0
        # Union-reservoir nearest-rank percentiles, exact while the
        # per-host reservoirs were exact.
        union = sorted([1.0, 2.0, 3.0, 10.0, 20.0, 30.0])
        assert h["p50"] == union[2]
        assert h["p95"] == union[5]
        assert h["reservoir_n"] == 6
        assert "approx" not in h

    def test_same_host_dedupes_latest_wins(self):
        a = self._host_snap(0, [1.0], shards=3)
        b = dict(a, ts=a["ts"] + 5.0, counters={"sweep/shards": 9})
        m = obs_metrics.merge_snapshots([a, b, a])
        assert m["hosts"] == 1
        assert m["counters"]["sweep/shards"] == 9  # cumulative: latest
        # Idempotent: re-feeding the same stream changes nothing.
        again = obs_metrics.merge_snapshots([a, b, b, a])
        assert again["counters"] == m["counters"]
        assert again["histograms"] == m["histograms"]

    def test_missing_reservoir_falls_back_to_approx(self):
        reg = obs_metrics.MetricsRegistry()
        reg.histogram("sweep/shard_seconds").observe(2.0)
        old = reg.snapshot(host={"hostname": "old", "pid": 1})
        new = self._host_snap(1, [4.0])
        m = obs_metrics.merge_snapshots([old, new])
        h = m["histograms"]["sweep/shard_seconds"]
        assert h["count"] == 2
        assert h["approx"] is True  # flagged, not silently exact-looking
        assert obs_metrics.validate_merged_snapshot(m) == []

    def test_empty_inputs_raise(self):
        with pytest.raises(ValueError):
            obs_metrics.merge_snapshots([])
        with pytest.raises(ValueError):
            obs_trace.merge_traces([])

    def test_schema_forward_backward(self):
        # Backward: a pre-fleet-merge snapshot (no host/clock/reservoir)
        # still validates.
        old = {"ts": 1.0, "counters": {"c": 1}, "histograms": {}}
        assert obs_metrics.validate_snapshot(old) == []
        # Forward: the new identity-stamped reservoir snapshot validates
        # and carries the fields the merge needs.
        reg = obs_metrics.MetricsRegistry()
        reg.histogram("h").observe(1.0)
        new = reg.snapshot(reservoir=True)
        assert obs_metrics.validate_snapshot(new) == []
        assert isinstance(new["host"]["hostname"], str)
        assert isinstance(new["clock"]["epoch_s"], (int, float))
        assert new["histograms"]["h"]["reservoir"] == [1.0]
        # New fields are validated when present.
        assert obs_metrics.validate_snapshot(
            dict(new, host={"hostname": 7})
        ) != []
        bad_h = dict(new["histograms"]["h"], reservoir="x")
        assert obs_metrics.validate_snapshot(
            dict(new, histograms={"h": bad_h})
        ) != []
        # Merged schema: fleet fields required on top of the base.
        merged = obs_metrics.merge_snapshots([new])
        assert obs_metrics.validate_merged_snapshot(merged) == []
        assert obs_metrics.validate_merged_snapshot(old) != []

    def test_merge_traces_offsets_and_pid_namespace(self):
        tr = obs_trace.enable()
        with obs_trace.span("a", "cat"):
            pass
        obs_trace.disable()
        t0 = tr.to_json()
        assert obs_trace.validate_trace(t0) == []
        assert isinstance(t0["host"]["hostname"], str)
        t1 = copy.deepcopy(t0)
        t1["clock"]["epoch0_s"] = t0["clock"]["epoch0_s"] + 1.0
        t1["host"] = dict(t1["host"], host_index=1)
        m = obs_trace.merge_traces([t0, t1])
        assert obs_trace.validate_trace(m) == []
        assert len(m["merged_from"]) == 2
        spans = [e for e in m["traceEvents"] if e.get("ph") == "X"]
        stride = obs_trace._MERGE_PID_STRIDE
        a = [e for e in spans if e["pid"] < stride]
        b = [e for e in spans if e["pid"] >= stride]
        assert len(a) == 1 and len(b) == 1  # per-host pid namespaces
        # The 1s epoch skew lands as exactly 1e6 us of timeline offset.
        assert math.isclose(
            b[0]["ts"] - a[0]["ts"], 1e6, rel_tol=1e-9
        )
        labels = [
            e for e in m["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        ]
        assert len(labels) >= 2


class TestObsMergeCLI:
    def test_metrics_merge_roundtrip(self, tmp_path):
        paths = []
        for i in range(2):
            p = str(tmp_path / f"m{i}.jsonl")
            reg = obs_metrics.MetricsRegistry()
            reg.counter("sweep/shards").inc(i + 1)
            reg.histogram("sweep/shard_seconds").observe(float(i + 1))
            reg.export_jsonl(
                p, reservoir=True,
                host={"host_index": i, "pid": 100 + i},
            )
            paths.append(p)
        out = str(tmp_path / "merged.json")
        r = _script("obs_merge.py", "metrics", *paths, "--out", out)
        assert r.returncode == 0, r.stderr
        with open(out) as f:
            merged = json.load(f)
        assert obs_metrics.validate_merged_snapshot(merged) == []
        assert merged["hosts"] == 2
        assert merged["counters"]["sweep/shards"] == 3
        r2 = _cli("validate", "--kind", "merged", out)
        assert r2.returncode == 0, r2.stderr

    def test_traces_merge_roundtrip(self, tmp_path):
        tp = str(tmp_path / "t.json")
        tr = obs_trace.enable(tp)
        with obs_trace.span("x"):
            pass
        obs_trace.disable()
        out = str(tmp_path / "merged_trace.json")
        r = _script("obs_merge.py", "traces", tp, tp, "--out", out)
        assert r.returncode == 0, r.stderr
        with open(out) as f:
            merged = json.load(f)
        assert obs_trace.validate_trace(merged) == []
        r2 = _cli("validate", out)
        assert r2.returncode == 0, r2.stderr


class TestSignatureCLI:
    def test_signature_subcommand_renders_overlay(self, tmp_path):
        path = str(tmp_path / "sig.jsonl")
        stream = obs_signature.SignatureStream(path)
        for sched in (Schedule.SERIAL, Schedule.UNIFORM_FUSED_1D):
            stream.observe_decision(GEMM, TPU_V5E, sched, group=8,
                                    source="analytic")
        stream.export_jsonl()
        r = _cli("validate", "--kind", "signature", path)
        assert r.returncode == 0, r.stderr
        r2 = _cli("signature", path)
        assert r2.returncode == 0, r2.stderr
        assert "uniform-fused-1d" in r2.stdout
        assert obs_signature.machine_family(TPU_V5E.name) in r2.stdout
        assert "exposed_comm_s" in r2.stdout

    def test_validate_rejects_malformed_signature(self, tmp_path):
        path = str(tmp_path / "bad.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 0.0}) + "\n")
        assert _cli("validate", "--kind", "signature", path).returncode == 1
