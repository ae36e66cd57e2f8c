"""The plain float32 reference against the program's forward, at a small
size, for both layer kinds the configurations use; and the seeded
weights it shares with the program's parameters."""

import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from chipbench import harness  # noqa: E402

dense = harness.Files(BENCH.parents[1]).module("families", "dense")
reference = weights = dense
Dims = dense.Dims

# OLMo's kind (non-parametric LayerNorm, tied embeddings, MHA) and Yi's
# (RMSNorm, GQA, untied output), at widths a CPU test can hold.
KINDS = {
    "olmo": Dims(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                 head_dim=16, d_ff=128, vocab_size=96,
                 norm="nonparametric_ln", norm_eps=1e-6,
                 tie_embeddings=True, rope_theta=10000.0, dtype="float32"),
    "yi": Dims(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
               head_dim=16, d_ff=128, vocab_size=96, norm="rmsnorm",
               norm_eps=1e-6, tie_embeddings=False, rope_theta=10000.0,
               dtype="float32"),
}
ARCH = {"olmo": "olmo-1b", "yi": "yi-9b"}


def _program(kind):
    from repro.models.model import build_model

    program_config = dense.program_config

    m = KINDS[kind]
    cfg = {"arch": ARCH[kind], "model": dataclasses.asdict(m),
           "overlap": {"mode": "gspmd_serial", "backend": "xla"},
           "options": {"remat": False}}
    return m, build_model(program_config(cfg))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_reference_matches_the_program_forward(kind):
    m, model = _program(kind)
    seed = 5
    params = jax.jit(functools.partial(weights.program_params, m))(
        weights.seed_key(seed))
    tokens = np.random.default_rng(0).integers(0, m.vocab_size, (2, 24),
                                               dtype=np.int32)
    want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    blocks = [jnp.asarray(tokens[i:i + 1]) for i in range(2)]
    h = reference.hidden(m, seed, blocks)
    u = weights.unembed(m, weights.seed_key(seed)).astype(jnp.float32)
    got = jnp.stack([reference.logits(m, x[0], u) for x in h])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the program's own top tokens sit at the reference's best
    gaps, _ = reference.token_gaps(
        m, seed, blocks, [jnp.argmax(want[i:i + 1], -1) for i in range(2)])
    assert max(float(jnp.max(g)) for g in gaps) < 1e-4


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_reference_loss_matches_the_program_loss(kind):
    m, model = _program(kind)
    seed = 9
    params = jax.jit(functools.partial(weights.program_params, m))(
        weights.seed_key(seed))
    tokens = np.random.default_rng(1).integers(0, m.vocab_size, (3, 16),
                                               dtype=np.int32)
    want, _ = model.loss(params, {"tokens": tokens, "labels": tokens})
    got, grads = reference._loss_and_grads(
        m, reference.initial_params(m, seed), jnp.asarray(tokens), None,
        block=2)
    assert got == pytest.approx(float(want), rel=1e-5)
    g_prog = jax.grad(lambda p: model.loss(
        p, {"tokens": tokens, "labels": tokens})[0])(params)
    for name in weights.leaf_groups(m):
        np.testing.assert_allclose(
            grads[name], weights.program_leaf(g_prog, name), rtol=1e-3,
            atol=1e-6, err_msg=name)


def test_weights_are_the_same_made_whole_or_layer_by_layer():
    m = KINDS["yi"]
    key = weights.seed_key(2**40 + 3)
    whole = weights.program_params(m, key)
    for l in range(m.num_layers):
        one = weights.layer_weights(m, key, l)
        for name in weights.LAYER_LEAVES:
            np.testing.assert_array_equal(
                weights.program_leaf(whole, name)[l], one[name])


def test_seeds_that_differ_above_32_bits_give_different_weights():
    m = KINDS["olmo"]
    a = weights.embed(m, weights.seed_key(7))
    b = weights.embed(m, weights.seed_key(7 + 2**32))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, weights.embed(m, weights.seed_key(7)))


def test_fp8_control_rounds_to_four_significant_bits():
    x = jnp.asarray([1.0, 1.06, 3.3, -448.0])
    q = reference._fp8(x)
    # per-tensor scale 1: 1.06 -> 1.0 or 1.125, 3.3 -> 3.25
    np.testing.assert_allclose(q, [1.0, 1.0, 3.25, -448.0])
