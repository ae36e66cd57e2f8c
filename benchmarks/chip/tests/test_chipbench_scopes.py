"""The program's scopes, and the join of a trace's ops to them.

* The program's named scopes change its compiled programs' metadata and
  at most the names of a few instructions, never what they compute.
* Every scope string a reader hard-codes is in its cell's program.
* Each op event joins the instruction of its name and signature, and
  the readers sum device self time by scope.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness, scopes, trace  # noqa: E402

MS = 1_000_000  # ns


def _programs() -> list:
    """(cell, overlap backend): each cell of BENCHMARK.json, its program
    as the cell runs it, and each cell on the Pallas DMA backend again on
    the XLA schedules in place of the DMA kernel."""
    files = harness.Files(ROOT)
    out = []
    for w in files.spec()["workloads"]:
        out.append((w["name"], ""))
        overlap = files.cell(w["name"])["config"].get("overlap") or {}
        if overlap.get("backend") == "pallas_dma":
            out.append((w["name"], "xla"))
    return out


PROGRAMS = _programs()


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    """The driver's reading of each program in ``PROGRAMS``, each in a
    process of its own with four CPU devices."""
    out = {}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    for cell, backend in PROGRAMS:
        tmp = tmp_path_factory.mktemp("scopes")
        proc = subprocess.run(
            [sys.executable, str(HERE / "scopes_driver.py"), str(tmp), cell]
            + ([backend] if backend else []),
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out[cell, backend] = json.loads(proc.stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("cell,backend", PROGRAMS)
def test_scopes_leave_the_compiled_program_as_it_was(compiled, cell,
                                                     backend):
    got = compiled[cell, backend]
    # the same instructions in the same order, once renamed by order;
    # on the CPU the prefill program keeps even its names
    assert got["canonical"], got
    if cell.startswith("yi9b"):
        assert got["identical"], got
    assert got["plain_scopes"] == []
    # the program the readers join is the one the cell's loop runs
    assert got["as_the_loop_runs"], got


def test_the_program_emits_every_scope_a_reader_hard_codes(compiled):
    files = harness.Files(ROOT)
    checked = 0
    for m in files.spec()["per_layer"]:
        reader = files.module("metrics", m["name"])
        wanted = [getattr(reader, k) for k in ("SCOPE", "LAYERS")
                  if hasattr(reader, k)]
        for cell in m.get("workloads", []):
            for s in wanted:
                assert s in compiled[cell, ""]["scopes"], (m["name"], s)
                checked += 1
    assert checked >= 5


def test_chain_reads_the_program_scopes_along_an_op_name():
    assert scopes.chain(
        "jit(prefill)/layers/while/body/mlp/up_gate/ficco/exchange/"
        "shard_map/pallas_call") == (
        "layers", "mlp", "mlp/up_gate", "ficco", "ficco/exchange")
    assert scopes.chain("jit(serve_step)/layers/while/body/closed_call/"
                        "attn/kv_cache/dynamic_update_slice") == (
        "layers", "attn", "kv_cache")
    assert scopes.chain("jit(prefill)/norm/mean") == ("norm",)
    # an inlined call repeats its caller's path once more
    assert scopes.chain("jit(f)/layers/attn/core/jit(g)/attn/core/dot") == (
        "layers", "attn", "attn/core")
    # a child name counts only under its parent
    assert scopes.chain("jit(f)/o/core/jit(norm)/exchange") == ()
    assert scopes.chain("") == ()


def test_signature_keeps_name_result_type_and_operand_names():
    """The trace's form (operand types, ``async-start``) and the module's
    reduce to the same signature."""
    module = ('  ROOT %fusion.3 = bf16[4,8]{1,0:T(8,128)} fusion(%p.1, %c), '
              'kind=kLoop, calls=%f, metadata={op_name="a"}')
    event = ("%fusion.3 = bf16[4,8]{1,0:T(8,128)} fusion(bf16[4,8]{1,0} "
             "%p.1, f32[] %c), kind=kLoop, calls=%f")
    want = "%fusion.3 = bf16[4,8]{1,0:T(8,128)}(%p.1, %c)"
    assert scopes.signature(module) == (want, True)
    assert scopes.signature(event) == (want, True)
    assert scopes.opcode(module) == "fusion"
    tup = "%s.2 = (s32[], bf16[4]{0}) slice-start(%t), x"
    traced = "%s.2 = (s32[], bf16[4]{0}) async-start((s32[]) %t), x"
    assert scopes.signature(tup) == scopes.signature(traced) == (
        "%s.2 = (s32[], bf16[4]{0})(%t)", True)
    # cut short in the operands, in the result type, before the opcode
    assert scopes.signature("%f.3 = bf16[4]{0} fusion(bf16[4") == (
        "%f.3 = bf16[4]{0}(", False)
    assert scopes.signature("%f.3 = (bf16[4]{0}, f32[2") == (
        "%f.3 = (bf16[4]{0}, f32[2", False)


# A module as the compiler prints one: a layer scan whose body holds a
# GEMM in each of two scopes, a copy the compiler put in, with no
# metadata, and a kernel whose attributes run over three lines; and an
# entry with the embedding outside the scan.
MODULE = '''HloModule jit_prefill, entry_computation_layout={()->f32[]}

FileNames
1 "model.py"

%fused.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(f32[8]{0} %p), metadata={op_name="jit(prefill)/layers/while/body/mlp/up_gate/neg"}
}

%body.7 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %gte.1), kind=kLoop, calls=%fused.1, metadata={op_name="jit(prefill)/layers/while/body/mlp/up_gate/ficco/exchange/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kOutput, calls=%fused.1, metadata={op_name="jit(prefill)/layers/while/body/mlp/down/dot_general"}
  %copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.2)
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %copy.3), kind=kOutput, calls=%fused.1, metadata={op_name="jit(prefill)/layers/while/body/attn/qkv/dot_general"}
  %exchange.9 = f32[4,8]{1,0} custom-call(f32[8]{0} %fusion.4), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"mesh_axes":"[\"model\"]"
}}, metadata={op_name="jit(prefill)/layers/while/body/mlp/up_gate/shard_map/ficco/exchange/pallas_call"}
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(s32[] %i, f32[8]{0} %fusion.4)
}

ENTRY %main.9 () -> f32[] {
  %fusion.6 = f32[8]{0} fusion(s32[4]{0} %tok), kind=kLoop, calls=%fused.1, metadata={op_name="jit(prefill)/embed/gather"}
  %while.8 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%cond.1, body=%body.7, metadata={op_name="jit(prefill)/layers/while"}
  ROOT %r = f32[] reduce(f32[8]{0} %x, f32[] %z), to_apply=%add
}
'''


def _event(table_text: str, name: str) -> str:
    """An op's event name as a TPU trace prints it: the instruction's
    text without its metadata."""
    for line in table_text.splitlines():
        if line.strip().startswith(f"%{name} = "):
            return line.strip().split(", metadata=")[0]
    raise KeyError(name)


def test_join_finds_each_instruction_and_its_inherited_scope():
    table = scopes.parse(MODULE)

    def chain(event):
        name = scopes.join(event, table)
        return None if name is None else table[name][1]

    ev = lambda n: _event(MODULE, n)  # noqa: E731
    assert scopes.join(ev("fusion.1"), table) == "fusion.1"
    assert chain(ev("fusion.1")) == (
        "layers", "mlp", "mlp/up_gate", "ficco", "ficco/exchange")
    assert chain(ev("fusion.2")) == ("layers", "mlp", "mlp/down")
    # no metadata of its own: the scope of the loop whose body holds it
    assert chain(ev("copy.3")) == ("layers",)
    assert chain(ev("fusion.6")) == ("embed",)
    assert chain(ev("while.8")) == ("layers",)
    assert chain(ev("exchange.9")) == (
        "layers", "mlp", "mlp/up_gate", "ficco", "ficco/exchange")
    # a name the program has, with another signature, joins nothing
    assert chain("%fusion.2 = f32[16]{0} fusion(f32[16]{0} %x)") is None
    assert chain("%nothing.1 = f32[] add(f32[] %a, f32[] %b)") is None
    # a text the trace cut short joins by what it has
    assert chain(ev("fusion.2")[:30]) == ("layers", "mlp", "mlp/down")
    assert chain(ev("exchange.9")[:60]) == chain(ev("exchange.9"))
    assert table["exchange.9"][2] == "custom-call"
    assert scopes.result_dims(ev("fusion.2")) == "8"
    assert scopes.result_dims(ev("while.8")) == ""


def test_join_reads_a_compiled_program_with_nested_scopes():
    import jax
    import jax.numpy as jnp

    def f(x, ws):
        def body(h, w):
            with jax.named_scope("attn"):
                with jax.named_scope("qkv"):
                    h = jnp.tanh(h @ w)
            with jax.named_scope("mlp"):
                with jax.named_scope("down"):
                    h = h @ w.T
            return h, None

        with jax.named_scope("layers"):
            h, _ = jax.lax.scan(body, x, ws)
        with jax.named_scope("logits"):
            return h.sum()

    x = jnp.ones((8, 16))
    ws = jnp.ones((3, 16, 16))
    text = jax.jit(f).lower(x, ws).compile().as_text()
    table = scopes.parse(text)
    chains = {}
    for line in text.splitlines():
        ev = line.strip().split(", metadata=")[0]
        if " dot(" in ev or " while(" in ev:
            chains[ev.split(" = ")[0]] = table[scopes.join(ev, table)][1]
    assert len(chains) >= 3
    assert ("layers", "attn", "attn/qkv") in chains.values()
    assert ("layers", "mlp", "mlp/down") in chains.values()
    assert ("layers",) in chains.values()  # the scan's loop


def _run_with(monkeypatch, devices, steps, cell="tiny-cell",
              module=MODULE):
    """A run whose trace holds ``devices`` and whose command line names
    ``cell``, with ``module`` as that cell's compiled program and a cache
    of 2 x 8 x 4 x 16 a layer."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell])
    monkeypatch.setitem(scopes._TABLES, cell, scopes.parse(module))
    monkeypatch.setattr(scopes, "cell", lambda: {
        "traffic": {"batch": 2, "cache_len": 8},
        "config": {"model": {"num_kv_heads": 4, "head_dim": 16}}})
    t = trace.from_events(devices, [(0, 100 * MS, trace.WINDOW_SPAN)])

    class Run:
        records = {"steps": steps}

    Run.trace = t
    return Run


def _reader(name):
    return harness.Files(ROOT).module("metrics", name).read


def test_readers_sum_self_time_by_scope_per_step(monkeypatch):
    ev = lambda n: _event(MODULE, n)  # noqa: E731
    # device 0: the scan 0-90 holds the exchange 0-20, down 20-50, the
    # copy 50-60 and qkv 60-80; the embedding 90-95.  Device 1 the same,
    # but its exchange takes 10 ms more and an eager op joins nothing.
    dev0 = [(0, 90 * MS, ev("while.8")), (0, 20 * MS, ev("fusion.1")),
            (20 * MS, 50 * MS, ev("fusion.2")),
            (50 * MS, 60 * MS, ev("copy.3")),
            (60 * MS, 80 * MS, ev("fusion.4")),
            (90 * MS, 95 * MS, ev("fusion.6"))]
    dev1 = [(0, 90 * MS, ev("while.8")), (0, 30 * MS, ev("fusion.1")),
            (30 * MS, 50 * MS, ev("fusion.2")),
            (50 * MS, 60 * MS, ev("copy.3")),
            (60 * MS, 80 * MS, ev("fusion.4")),
            (95 * MS, 96 * MS, "%argmax.1 = s32[4]{0} reduce(f32[4]{0} %l)")]
    run = _run_with(monkeypatch, {0: dev0, 1: dev1}, steps=2)
    # per device, per step: (20 + 30) / 2 / 2 ms
    assert _reader("up_gate_ms.prefill")(run) == pytest.approx(12.5)
    assert _reader("down_ms.prefill")(run) == pytest.approx(12.5)
    assert _reader("attn_ms.prefill")(run) == pytest.approx(10.0)
    # no kv_cache scope in this program: None, not the loop's own time
    assert _reader("kv_cache_ms.decode")(run) is None
    table = scopes.parse(MODULE)
    by_name, joined = scopes.self_seconds(run.trace, table)
    secs = scopes.by_chain(by_name, table)
    assert by_name["while.8"] == pytest.approx(0.010)  # the loop's own
    assert secs[("layers",)] == pytest.approx(0.020)  # and the copy
    assert secs[None] == pytest.approx(0.0005)
    assert joined == pytest.approx(1 - 0.0005 / sum(secs.values()))


def test_kv_cache_counts_its_scope_and_the_scans_cache_moves(monkeypatch):
    """The scan's ops count where their result is a layer's cache (2 x 8
    x 4 x 16 here) or the stack of them; a weight's copy does not."""
    module = MODULE.replace("mlp/down/dot_general", "attn/kv_cache/dus")
    module = module.replace(
        "%copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.2)",
        "%copy.3 = f32[2,8,4,16]{3,2,1,0} copy(f32[8]{0} %fusion.2)\n"
        "  %copy.10 = f32[3,2,8,4,16]{4,3,2,1,0} copy(f32[8]{0} %fusion.2)\n"
        "  %copy.11 = f32[1,16,16]{2,1,0} copy(f32[8]{0} %fusion.2)")
    # a cache write the compiler fused into RoPE's op, in attn/qkv
    module = module.replace(
        "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %copy.3)",
        "%fusion.4 = f32[2,8,4,16]{3,2,1,0} fusion(f32[8]{0} %copy.3)")
    ev = lambda n: _event(module, n)  # noqa: E731
    ops = [(0, 90 * MS, ev("while.8")), (0, 20 * MS, ev("fusion.1")),
           (20 * MS, 50 * MS, ev("fusion.2")),
           (50 * MS, 60 * MS, ev("copy.3")),
           (60 * MS, 64 * MS, ev("copy.10")),
           (64 * MS, 66 * MS, ev("copy.11")),
           (66 * MS, 80 * MS, ev("fusion.4"))]
    run = _run_with(monkeypatch, {0: ops}, steps=10, module=module)
    # kv_cache 30 ms, the cache's copies 14 ms and the fused write 14 ms,
    # per step; not the weight's copy (2 ms) nor the loop's own (4 ms)
    assert _reader("kv_cache_ms.decode")(run) == pytest.approx(5.8)


def test_readers_read_none_where_nothing_can_be_joined(monkeypatch):
    ev = lambda n: _event(MODULE, n)  # noqa: E731
    ops = [(0, 20 * MS, ev("fusion.1"))]
    run = _run_with(monkeypatch, {0: ops}, steps=1)
    assert _reader("up_gate_ms.prefill")(run) == pytest.approx(20.0)
    # the command line names no cell
    unnamed = _run_with(monkeypatch, {0: ops}, steps=1)
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert _reader("up_gate_ms.prefill")(unnamed) is None
    # a program without the scopes, as a parent without them compiles
    plain = _run_with(monkeypatch, {0: ops}, steps=1, cell="plain",
                      module=MODULE.replace("mlp/up_gate/", ""))
    assert _reader("up_gate_ms.prefill")(plain) is None
    # the trace's ops are not the compiled program's
    other = _run_with(monkeypatch, {0: [(0, 20 * MS, "%fusion.1 = f32[9]")]},
                      steps=1, cell="other")
    assert _reader("up_gate_ms.prefill")(other) is None


def test_idle_gaps_name_a_gap_by_the_program_span_inside_a_bench_span():
    """Once the trace holds the program's spans, each gap goes to the
    innermost span: the engine's phase, not the benchmark's call."""
    ops = [(0, 10 * MS, "%fusion.1"), (14 * MS, 20 * MS, "%fusion.1"),
           (21 * MS, 100 * MS, "%fusion.1")]
    spans = [(0, 100 * MS, trace.WINDOW_SPAN),
             (0, 100 * MS, "bench/engine.run"),
             (1 * MS, 99 * MS, "serve/run"),
             (9 * MS, 15 * MS, "serve/step"),
             (9 * MS + 500, 15 * MS - 500, "serve/fetch"),
             (19 * MS, 22 * MS, "serve/step"),
             (19 * MS + 500, 20 * MS, "serve/fetch"),
             (20 * MS, 22 * MS, "serve/feed")]
    t = trace.from_events({0: ops}, spans)
    gaps = dict(trace.idle_gaps(t))
    assert gaps == {"serve/fetch": pytest.approx(0.004),
                    "serve/feed": pytest.approx(0.001)}
