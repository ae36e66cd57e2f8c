"""The harness finds each cell's files by name, so a cell, a configuration,
a mix and a per-layer metric are added by adding files and entries; and
it refuses to run without a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH / "tests"))

import tinytree  # noqa: E402
from chipbench import harness  # noqa: E402

TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
            head_dim=16, d_ff=128, vocab_size=128, dtype="float32")

READER = '''"""steps_seen.train: steps in the window (a test's added metric)."""


def read(run):
    return float(run.records["steps"])
'''


def _added_tree(tmp_path):
    """The repository's benchmark files, plus one configuration, one mix,
    one cell and one per-layer metric added as new files and entries."""
    root, bench = tmp_path, tmp_path / "bench"
    for sub in ("configs", "families", "traffic", "cells", "metrics",
                "generators", "loops"):
        shutil.copytree(BENCH / sub, bench / sub)
    cfg = json.loads((BENCH / "configs/olmo-1b-stage8.json").read_text())
    cfg["model"].update(TINY)
    (bench / "configs/tiny-olmo.json").write_text(json.dumps(cfg))
    (bench / "traffic/tiny-lm.json").write_text(json.dumps(
        {"loop": "train", "generator": "lm_stream", "batch": 2,
         "seq_len": 16, "distinct_batches": 4}))
    (bench / "cells/tiny-train.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1e-3, "grad_norm_gap": 1e-3,
                    "change_norm_gap": 1e-3}}))
    (bench / "metrics/steps_seen.train.py").write_text(READER)
    spec = tinytree.with_train_cell(
        json.loads((ROOT / "BENCHMARK.json").read_text()))
    spec["configs"].append({"name": "tiny-olmo", "source": "test",
                            "file": "bench/configs/tiny-olmo.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-train", "config": "tiny-olmo",
                              "traffic": "tiny-lm", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-train")
    spec["per_layer"].append({
        "name": "steps_seen.train", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "launcher and harness",
        "moves": "train_tokens_per_s", "workloads": ["tiny-train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Files(root, bench)


def test_added_files_are_found_by_name(tmp_path):
    files = _added_tree(tmp_path)
    c = files.cell("tiny-train")
    assert c["config"]["model"]["d_model"] == 64
    assert c["traffic"]["seq_len"] == 16
    assert c["limits"]["limits"]["loss_gap"] == 1e-3
    assert [m["name"] for m in c["end_to_end"]] == [
        "setup_s", "train_tokens_per_s"]
    assert [m["name"] for m in c["per_layer"]] == ["steps_seen.train"]

    class Run:
        records = {"steps": 7}

    assert files.reader("steps_seen.train")(Run) == 7.0
    # the cells already there are untouched
    assert files.cell("olmo1b-decode-chat")["config"]["model"][
        "d_model"] == 2048


def test_an_added_cell_runs_end_to_end(tmp_path):
    files = _added_tree(tmp_path)
    res = harness.run_cell(files, "tiny-train", 2**31 + 11, 0.5, False,
                           require_chip=False, compile_cache=False)
    assert res["correct"] is True, res["checked"]
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert res["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert list(res)[-1] == "checked"
    assert set(res["checked"]) == {"loss_gap", "grad_norm_gap",
                                   "change_norm_gap"}


GENERATOR = '''"""Chats that share one system prompt (a test's added kind)."""

import numpy as np


def batch(mix, vocab, seed, i):
    rng = np.random.default_rng((seed, 9, i))
    shared = np.random.default_rng((seed, 9)).integers(
        0, vocab, mix["prefix"], dtype=np.int32)
    return [(np.concatenate([shared, rng.integers(0, vocab, 3,
                                                  dtype=np.int32)]),
             mix["new_tokens"]) for _ in range(mix["batch"])]
'''

LOOP = '''"""Draws the generator's batches and runs nothing (a test's added
kind of loop)."""

import time

from chipbench.family import Work


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx

    def setup(self):
        pass

    def window(self, seconds):
        t0, n = time.perf_counter(), 0
        while time.perf_counter() - t0 < seconds:
            self.rows = len(self.ctx.batch(n))
            n += 1
        elapsed = time.perf_counter() - t0
        return {"attempted": n, "failed": 0, "elapsed": elapsed,
                "steps": n, "work": Work(),
                "metrics": {"batches_per_s": n / elapsed}}

    def release(self):
        pass

    def check(self, control=False):
        return {"rows short": (self.ctx.mix["batch"] - self.rows, 0)}
'''


def _new_kind_tree(tmp_path, loop: str):
    """The tiny tree plus a mix of a new kind (a new generator, and
    ``loop``: an existing loop or a new one), added as files and
    entries alone."""
    root, bench = tinytree.make(tmp_path)
    (bench / "generators/shared_prefix.py").write_text(GENERATOR)
    (bench / "loops/draw_only.py").write_text(LOOP)
    (bench / "traffic/shared-prefix.json").write_text(json.dumps(
        {"loop": loop, "generator": "shared_prefix", "batch": 4,
         "cache_len": 24, "prefix": 9, "new_tokens": 5}))
    (bench / "cells/tiny-shared.json").write_text(json.dumps(
        {"limits": {"token_gap": 0.15}, "check_requests": 2}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-shared", "config": "olmo-1b",
                              "traffic": "shared-prefix", "chips": 1,
                              "why": "test"})
    metric = ("decode_tokens_per_s" if loop == "decode"
              else "batches_per_s")
    if loop != "decode":
        spec["end_to_end"].append({
            "name": metric, "unit": "1/s", "better": "higher",
            "bound": 0.05, "source": "host_clock", "workloads": []})
    for m in spec["end_to_end"]:
        if m["name"] == metric:
            m["workloads"].append("tiny-shared")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Files(root, bench), metric


@pytest.mark.parametrize("loop", ["decode", "draw_only"])
def test_a_mix_of_a_new_kind_is_added_by_files_alone(tmp_path, loop):
    before = {p: p.read_bytes() for p in BENCH.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    files, metric = _new_kind_tree(tmp_path, loop)
    res = harness.run_cell(files, "tiny-shared", 2**31 + 17, 0.2, False,
                           require_chip=False, compile_cache=False)
    assert res["correct"] is True, res["checked"]
    assert set(res["metrics"]) == {"setup_s", metric}
    assert res["attempted"] > 0
    after = {p: p.read_bytes() for p in BENCH.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before


FAMILY = '''"""The dense family under another name (a test's added family)."""

import pathlib

from chipbench import harness

_bench = pathlib.Path(__file__).resolve().parents[1]
dense = harness.Files(_bench, _bench).module("families", "dense")

Dims, dims, program_config = dense.Dims, dense.dims, dense.program_config
program_params, leaf_groups, program_leaf = (
    dense.program_params, dense.leaf_groups, dense.program_leaf)
prefill, decode_batch, train_step = (
    dense.prefill, dense.decode_batch, dense.train_step)
token_gaps, train_reference = dense.token_gaps, dense.train_reference
'''

LOGITS_LOOP = '''"""A closed loop of the model's forward on one device, its argmax
compared at every position (a test's added kind of loop, with its own
program)."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.family import Work
from chipbench.loops import gap_numbers, make_params


def program(ctx):
    from repro.models.model import build_model
    from repro.serve.engine import make_prefill

    model = build_model(ctx.family.program_config(ctx.config))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((ctx.mix["batch"], ctx.mix["seq_len"]),
                                jnp.int32)
    return jax.jit(make_prefill(model)), (params, {"tokens": toks}), None


class Loop:
    def __init__(self, ctx):
        self.ctx = ctx

    def setup(self):
        self.fn, (want, _), _ = program(self.ctx)
        self.params = make_params(self.ctx, want)
        self.tokens = jnp.asarray(self.ctx.batch(0))
        self.fn(self.params, {"tokens": self.tokens}).block_until_ready()

    def window(self, seconds):
        t0, n = time.perf_counter(), 0
        while n == 0 or time.perf_counter() - t0 < seconds:
            out = self.fn(self.params, {"tokens": self.tokens})
            out.block_until_ready()
            n += 1
        elapsed = time.perf_counter() - t0
        self.chosen = np.asarray(jnp.argmax(out, -1))
        b, s = self.tokens.shape
        w = self.ctx.family.prefill(self.ctx.dims, b, s)
        return {"attempted": n, "failed": 0, "elapsed": elapsed,
                "steps": n, "work": Work(w.flops * n, w.bytes * n),
                "metrics": {"logits_per_s": n * b * s / elapsed}}

    def release(self):
        self.params = self.fn = None

    def check(self, control=False):
        toks = np.asarray(self.tokens)
        rows = range(len(toks))
        gaps, cgaps = self.ctx.family.token_gaps(
            self.ctx.dims, self.ctx.seed,
            [jnp.asarray(toks[i:i + 1]) for i in rows],
            [jnp.asarray(self.chosen[i:i + 1]) for i in rows],
            control=control)
        return gap_numbers(gaps, cgaps, [np.ones((1, toks.shape[1]), bool)
                                          for _ in rows], control,
                           self.ctx.limits["limits"]["token_gap"])
'''

SCOPE_READER = '''"""attn_ms.logits: device ms per call in the attention scope (a test's
added scope metric)."""

from chipbench import scopes

SCOPE = "attn"


def read(run):
    return scopes.ms_per_step(run, SCOPE)
'''


def _new_family_checkout(tmp_path):
    """A copy of the repository's benchmark (BENCHMARK.json and its
    directory), plus a family, a configuration of it, a mix with a loop
    of its own, a cell and a scope metric, each with its tiny file where
    it needs one, added as new files and entries alone."""
    src = tmp_path / "checkout"
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = src / spec["paths"][0]
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "families/toy.py").write_text(FAMILY)
    cfg = json.loads((bench / "configs/olmo-1b.json").read_text())
    cfg["family"] = "toy"
    (bench / "configs/toy-olmo.json").write_text(json.dumps(cfg))
    (bench / "tests/tiny/configs/toy-olmo.json").write_text(json.dumps(
        {"model": dict(TINY, dtype="bfloat16")}))
    (bench / "loops/logits.py").write_text(LOGITS_LOOP)
    (bench / "traffic/logits-1k.json").write_text(json.dumps(
        {"loop": "logits", "generator": "uniform_tokens", "batch": 2,
         "seq_len": 1024}))
    (bench / "tests/tiny/traffic/logits-1k.json").write_text(json.dumps(
        {"seq_len": 16}))
    (bench / "cells/toy-logits.json").write_text(json.dumps(
        {"limits": {"token_gap": 0.15}}))
    (bench / "metrics/attn_ms.logits.py").write_text(SCOPE_READER)
    spec["configs"].append({"name": "toy-olmo", "source": "test",
                            "file": f"{spec['paths'][0]}/configs/"
                                    "toy-olmo.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy-logits", "config": "toy-olmo",
                              "traffic": "logits-1k", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({
        "name": "logits_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["toy-logits"]})
    spec["per_layer"].append({
        "name": "attn_ms.logits", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model step",
        "moves": "logits_per_s", "workloads": ["toy-logits"]})
    (src / "BENCHMARK.json").write_text(json.dumps(spec))
    return src


def test_a_family_and_a_loop_of_a_new_kind_are_added_by_files_alone(
        tmp_path):
    from chipbench import scopes

    before = {p: p.read_bytes() for p in BENCH.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    src = _new_family_checkout(tmp_path)
    assert tinytree.missing(src) == []
    root, bench = tinytree.make(tmp_path / "tiny", src)
    files = harness.Files(root, bench)
    c = files.cell("toy-logits")
    assert c["config"]["family"] == "toy"
    assert c["config"]["model"]["d_model"] == TINY["d_model"]
    assert c["traffic"]["seq_len"] == 16
    assert files.make_loop(c, 1).ctx.family.dims(c["config"]).d_model == 64
    res = harness.run_cell(files, "toy-logits", 2**31 + 19, 0.2, False,
                           require_chip=False, compile_cache=False)
    assert res["correct"] is True, res["checked"]
    assert set(res["metrics"]) == {"setup_s", "logits_per_s"}
    # the scope readers compile the new loop's own program, which holds
    # the scope its metric reads
    fn, args, mesh = scopes.program(files, "toy-logits")
    assert mesh is None and args[1]["tokens"].shape == (2, 16)
    table = scopes.parse(scopes.compiled_text(fn, args))
    reader = files.module("metrics", "attn_ms.logits")
    assert any(reader.SCOPE in t[1] for t in table.values())
    after = {p: p.read_bytes() for p in BENCH.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before


def test_every_configuration_and_mix_has_its_tiny_file():
    lacking = tinytree.missing()
    assert not lacking, f"no tiny cut for: {', '.join(lacking)}"


def test_a_configuration_without_a_family_file_is_refused(tmp_path):
    files = _added_tree(tmp_path)
    c = files.cell("tiny-train")
    c["config"]["family"] = "no_such_family"
    with pytest.raises(SystemExit, match="no families file"):
        files.make_loop(c, 1)
    del c["config"]["family"]
    with pytest.raises(SystemExit, match="no families file"):
        files.make_loop(c, 1)


def test_an_unknown_loop_or_generator_is_refused(tmp_path):
    files, _ = _new_kind_tree(tmp_path, "no_such_loop")
    with pytest.raises(SystemExit, match="no loops file"):
        files.make_loop(files.cell("tiny-shared"), 1)


def test_an_unknown_workload_is_refused(tmp_path):
    files = _added_tree(tmp_path)
    with pytest.raises(SystemExit):
        files.cell("no-such-cell")


def test_without_a_tpu_the_run_exits_nonzero_before_any_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "olmo1b-decode-chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_a_traced_run_reports_the_cells_per_layer_metrics(tmp_path,
                                                          monkeypatch):
    """The traced branch, with the profiler's file replaced by a trace in
    which the device is busy for the first half of the window."""
    from chipbench import peaks, trace

    def fake_load(path):
        w = sorted(s for s in spans if s[2] == trace.WINDOW_SPAN)[0]
        mid = (w[0] + w[1]) // 2
        return trace.from_events({0: [(w[0], mid, "%fusion.1 = f32[]")]},
                                 [w])

    spans = []
    monkeypatch.setattr(trace, "load", fake_load)
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setitem(peaks.TABLE, "cpu", peaks.TABLE["TPU v5 lite"])

    import jax

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            import time
            self.t = time.perf_counter_ns()

        def __exit__(self, *exc):
            import time
            spans.append((self.t, time.perf_counter_ns(), self.name))

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    root, bench = tinytree.make(tmp_path / "tree")
    res = harness.run_cell(harness.Files(root, bench), "olmo1b-decode-chat",
                           7, 0.2, True, require_chip=False,
                           compile_cache=False)
    assert set(res["metrics"]) == {"mfu.decode", "idle_share.decode"}
    assert res["metrics"]["idle_share.decode"]["value"] == (
        pytest.approx(50.0, abs=1.0))
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] > 0
    assert res["roofline_bound"] == "memory"
    assert res["breakdown"]["device_ops"][0][0] == "%fusion.1 = f32[]"
    assert list(res)[-1] == "checked"
