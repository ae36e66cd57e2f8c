"""The MLA + MoE family (``families/mla_moe.py``) against the program, on
the tiny cut of ``deepseek-v2-lite-ep8`` with seeded random weights:
the program's forward and its decode through the cache against the
plain float32 reference, with the float8 control failing the same
tolerance; the held shares of the reference's expert layer against the
whole layer; the counters by hand at the cell's sizes; and what the
family refuses."""

import dataclasses
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import tinytree  # noqa: E402
from chipbench import harness, scopes  # noqa: E402
from chipbench.family import seed_key  # noqa: E402

NAME = "deepseek-v2-lite-ep8"
FILES = harness.Files(BENCH.parents[1])
FULL = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
TINY = tinytree.merged(FULL, json.loads(
    (BENCH / tinytree.TINY / "configs" / f"{NAME}.json").read_text()))
fam = FILES.family(FULL)

# Float32 throughout, the program and the reference alike: they differ
# then by the order of float32 sums (5e-7 of logits up to 0.6 here), so
# 2e-4 holds with room; the float8 control moves the logits by about
# 0.2 and fails it by far (checked below).
TOL = 2e-4


def _tiny32():
    cfg = dict(TINY, dtype="float32", options={"remat": False})
    return cfg, fam.dims(cfg)


def _program(cfg, m, seed):
    from repro.models.model import build_model

    model = build_model(fam.program_config(cfg))
    params = jax.jit(functools.partial(fam.program_params, m))(
        seed_key(seed))
    return model, params


def _reference_logits(m, seed, tokens, quant=None):
    blocks = [jnp.asarray(tokens[i:i + 1]) for i in range(len(tokens))]
    u = fam.unembed(m, seed_key(seed)).astype(jnp.float32)
    return jnp.stack([fam.logits(m, x[0], u, quant)
                      for x in fam.hidden(m, seed, blocks, quant)])


def test_the_tiny_cut_keeps_every_mechanism():
    _, m = _tiny32()
    assert m.first_dense == 1 and m.num_layers - m.first_dense == 2
    assert (m.router_experts, m.held, m.top_k, m.shared) == (16, 4, 6, 2)
    assert not m.norm_topk
    assert m.yarn.factor == 40.0


def test_the_weights_fill_the_programs_tree():
    cfg, m = _tiny32()
    model, params = _program(cfg, m, 3)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # the held experts are the uncut layer's experts 0..3
    whole = fam.moe_weights(m, seed_key(3), 1, experts=jnp.arange(16))
    np.testing.assert_array_equal(params["layers"][0]["ffn"]["w_up"][0],
                                  whole["e_up"][:4])


def test_reference_matches_the_program_forward():
    cfg, m = _tiny32()
    seed = 2**31 + 29
    model, params = _program(cfg, m, seed)
    tokens = np.random.default_rng(5).integers(0, m.vocab_size, (2, 20),
                                               dtype=np.int32)
    want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    got = _reference_logits(m, seed, tokens)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    ctl = _reference_logits(m, seed, tokens, "fp8")
    assert float(jnp.max(jnp.abs(ctl - want))) > 10 * TOL


def test_decode_through_the_cache_matches_the_reference_forward():
    """The engine's own step, its cache donated, fed one token a step as
    ``DecodeEngine.run`` feeds a prompt, gives the reference's full
    forward's logits at every position."""
    from repro.serve.engine import DecodeEngine

    cfg, m = _tiny32()
    seed = 2**31 + 31
    tokens = np.random.default_rng(6).integers(0, m.vocab_size, (2, 16),
                                               dtype=np.int32)
    want = _reference_logits(m, seed, tokens)
    model, params = _program(cfg, m, seed)
    eng = DecodeEngine(fam.program_config(cfg), params, batch_size=2,
                       cache_len=16)
    cache = eng.cache
    for pos in range(tokens.shape[1]):
        logits, cache = eng.step_fn(params, cache,
                                    jnp.asarray(tokens[:, pos:pos + 1]),
                                    jnp.int32(pos))
        np.testing.assert_allclose(logits[:, 0], want[:, pos], rtol=TOL,
                                   atol=TOL, err_msg=f"position {pos}")


def test_reference_shares_add_up_to_the_whole_layer():
    """The reference's routed part of 4 shares of 4 experts, and the
    shared experts once, give the 16-expert layer."""
    _, m = _tiny32()
    key = seed_key(11)
    whole = fam._f32(fam.moe_weights(m, key, 1, experts=jnp.arange(16)))
    h = jax.random.normal(jax.random.PRNGKey(0), (12, m.d_model))
    full = fam.routed(m, h, whole, None, first=0)
    parts = []
    for f in range(0, 16, 4):
        share = dict(whole, **{k: whole[k][f:f + 4]
                               for k in fam.EXPERT_LEAVES})
        parts.append(fam.routed(m, h, share, None, first=f))
    np.testing.assert_allclose(sum(parts) + fam.shared(h, whole, None),
                               full + fam.shared(h, whole, None),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-3
    assert float(jnp.max(jnp.abs(parts[0] - full))) > 1e-3


def test_yarn_of_the_reference_is_deepseeks():
    m = fam.dims(FULL)
    inv = fam.yarn_inv_freq(m)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert fam.softmax_scale(m) == pytest.approx(0.114721, abs=1e-6)


def test_decode_counts_at_the_cells_sizes():
    m = fam.dims(FULL)
    assert (m.num_layers, m.held, m.router_experts) == (27, 8, 64)
    attn = (2048 * 16 * 192 + 2048 * 512 + 2048 * 64 + 512 * 16 * 128 * 2
            + 16 * 128 * 2048)
    assert fam.attn_params(m) == attn == 13_762_560
    expert = 3 * 2048 * 1408
    reached = 8 * (1 - (58 / 64) ** 4)
    assert fam.held_reached(m, 4) == pytest.approx(reached)
    weights = (27 * attn + 3 * 2048 * 10944 + 26 * 2 * expert
               + 2048 * 102400) * 2 + 26 * 2048 * 64 * 4
    weights += 26 * reached * expert * 2
    # four requests at position 999: 1,000 cached positions each
    one = fam.decode_step(m, [999] * 4)
    kv = 27 * 576 * 2
    assert one.bytes == pytest.approx(weights + kv * (4 * 1000 + 4))
    # 3.38 GB of weights a step, and 124 MB of latent cache here
    assert weights == pytest.approx(3.38e9, rel=0.01)
    tok = (2 * 27 * attn + 2 * 3 * 2048 * 10944
           + 26 * 2 * (2048 * 64 + 2 * expert) + 2 * 2048 * 102400)
    routed = 26 * 4 * 6 * 8 / 64 * 2 * expert
    pairs = 27 * 2 * 16 * (2 * 512 + 64) * 4000
    assert one.flops == pytest.approx(4 * tok + routed + pairs)


def test_the_family_teaches_the_scope_join_its_scopes():
    assert {"mla", "moe"} <= set(scopes.TOP)
    assert scopes.chain("jit(serve_step)/layers/while/body/mla/expand/"
                        "dot_general") == ("layers", "mla", "mla/expand")
    assert scopes.chain("jit(f)/layers/moe/shared/mlp/up_gate/dot") == (
        "layers", "moe", "moe/shared", "mlp", "mlp/up_gate")


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
    ("topk_method", "group_limited_greedy"), ("routed_scaling_factor", 16),
    ("tie_word_embeddings", True)])
def test_what_the_program_does_not_run_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        fam.program_config(dict(FULL, **{key: value}))


def test_a_file_of_another_family_is_refused():
    olmo = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
    with pytest.raises((KeyError, ValueError)):
        fam.program_config(olmo)
    dense = FILES.module("families", "dense")
    with pytest.raises((KeyError, ValueError)):
        dense.program_config(FULL)


def test_the_held_share_must_lie_in_the_router():
    with pytest.raises(ValueError, match="experts"):
        fam.program_config(dict(FULL, held_first=60))


def test_dims_read_the_published_keys():
    m = fam.dims(FULL)
    assert dataclasses.astuple(m.yarn) == (40.0, 4096, 32.0, 1.0, 0.707,
                                           0.707)
    assert (m.d_model, m.num_heads, m.nope_dim, m.rope_dim, m.kv_rank,
            m.v_dim, m.d_ff, m.d_expert) == (2048, 16, 128, 64, 512, 128,
                                              10944, 1408)
    assert m.vocab_size == 102400 and m.dtype == "bfloat16"


# The new cell's step as the TPU compiler prints it, cut to one op of
# each kind the readers sort (names and types from a chip trace of the
# cell): an up-projection in ``mla/expand``, the latent cache's stack
# copied outside any scope, a grouped GEMM as the ``ragged-dot`` custom
# call with no scope, the scan's slice of the held experts' weights in
# ``layers``, the router in ``moe/router`` and the output projection.
STEP = '''HloModule jit_serve_step, entry_computation_layout={()->f32[]}

%body.7 (arg: (s32[], bf16[8])) -> (s32[], bf16[8]) {
  %arg = (s32[], bf16[8]{0}) parameter(0)
  %fusion.289 = bf16[4,2048,16,128]{1,3,2,0} fusion(bf16[16,128,512,1]{2,1,0,3} %bitcast.377), kind=kOutput, calls=%f, metadata={op_name="jit(serve_step)/layers/while/body/mla/expand/dot_general"}
  %dynamic-slice_bitcast_fusion.10 = bf16[8,2048,1408]{2,1,0} fusion(bf16[26,8,2048,1408]{3,2,1,0} %get-tuple-element.624, s32[] %i), kind=kLoop, calls=%f, metadata={op_name="jit(serve_step)/layers/while/body/dynamic_slice"}
  %ragged-dot-none.1 = bf16[24,1408]{1,0} custom-call(s32[1]{0} %get-tuple-element.543, s32[9]{0} %get-tuple-element.544), custom_call_target="tpu_custom_call"
  %fusion.300 = f32[4,64]{1,0} fusion(bf16[4,2048]{1,0} %x), kind=kOutput, calls=%f, metadata={op_name="jit(serve_step)/layers/while/body/moe/router/dot_general"}
  ROOT %tuple.5 = (s32[], bf16[8]{0}) tuple(s32[] %i, bf16[8]{0} %y)
}

ENTRY %main.9 () -> f32[] {
  %copy.28 = bf16[26,4,2048,512]{3,1,2,0} copy(bf16[26,4,2048,512]{3,2,1,0} %cache__layers___0___c_kv__.1)
  %while.8 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t), condition=%cond.1, body=%body.7, metadata={op_name="jit(serve_step)/layers/while"}
  %convolution_bitcast_fusion.1 = bf16[4,1,102400]{2,0,1} fusion(bf16[2048,102400]{1,0} %params__unembed__.1), kind=kOutput, calls=%f, metadata={op_name="jit(serve_step)/logits/dot_general"}
  ROOT %r = f32[] reduce(f32[8]{0} %x, f32[] %z), to_apply=%add
}
'''


def test_the_readers_count_what_the_compiler_leaves_outside_the_scopes(
        monkeypatch):
    from chipbench import trace

    ms = 1_000_000  # ns
    cell = "dsv2lite-ep8-decode-chat"
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell])
    monkeypatch.setitem(scopes._TABLES, cell, scopes.parse(STEP))
    monkeypatch.setattr(scopes, "cell", lambda: FILES.cell(cell))

    def event(name):
        line = next(x for x in STEP.splitlines()
                    if x.strip().startswith(f"%{name} = "))
        return line.strip().split(", metadata=")[0]

    ops = [(0, 100 * ms, event("while.8")),
           (0, 30 * ms, event("fusion.289")),  # mla
           (30 * ms, 40 * ms, event("copy.28")),  # mla: the cache moved
           (40 * ms, 45 * ms, event("dynamic-slice_bitcast_fusion.10")),
           (45 * ms, 47 * ms, event("ragged-dot-none.1")),  # moe
           (47 * ms, 48 * ms, event("fusion.300")),  # moe/router
           (100 * ms, 110 * ms, event("convolution_bitcast_fusion.1"))]

    window = trace.from_events({0: ops}, [(0, 120 * ms, trace.WINDOW_SPAN)])

    class Run:
        records = {"steps": 2}

    Run.trace = window

    assert FILES.reader("mla_ms.decode")(Run) == pytest.approx(20.0)
    assert FILES.reader("moe_ms.decode")(Run) == pytest.approx(4.0)
