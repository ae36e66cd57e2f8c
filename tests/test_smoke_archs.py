"""Per-architecture smoke tests (reduced configs, single CPU device).

For every assigned architecture: instantiate the REDUCED variant (2 layers,
d_model <= 256, <= 4 experts), run one forward + one train-style grad step
and one decode step, asserting output shapes and finiteness.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.launch.specs import concrete_batch, encoder_len
from repro.models.model import build_model
from repro.obs import metrics

SMOKE_SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")


def _reduced(name):
    return get_config(name).reduced()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_and_loss(arch):
    cfg = _reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = concrete_batch(cfg, SMOKE_SHAPE)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (*batch["tokens"].shape, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_grad_step(arch):
    cfg = _reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = concrete_batch(cfg, SMOKE_SHAPE)

    @jax.jit
    def step(p):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True
        )(p)
        return loss, grads

    loss, grads = step(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert flat, "no grads"
    total = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32)))) for g in flat)
    assert np.isfinite(total) and total > 0


def _expected_cache_writes(model, cache) -> dict:
    """Trace-time counts of the decode step's cache writes: a row in
    place for each attention / MLA leaf, a whole rewrite for each
    recurrent leaf, nothing for the read-only cross-attention K/V; once
    per traced layer (a scan traces its body once), in each stack (the
    leading dense layers, where a model has them, and the periods)."""
    stacks = [(model.pattern, model.n_periods, cache)]
    if model.n_lead:
        stacks = [(model.lead_pattern, model.n_lead, cache["lead"]),
                  (model.pattern, model.n_periods, cache["layers"])]
    want = {"token": 0, "state": 0}
    for pattern, n_periods, stack in stacks:
        traced = 1 if model.config.scan_layers and n_periods > 1 else (
            n_periods)
        for spec, leaves in zip(pattern, stack):
            n = sum(1 for name in leaves if not name.startswith("cross_"))
            kind = "token" if spec.mixer in ("attn", "mla") else "state"
            want[kind] += n * traced
    return want


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_step(arch):
    cfg = _reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache_len = 64
    enc_len = encoder_len(cfg, SMOKE_SHAPE) if cfg.encdec else 0
    cache = model.init_cache(2, cache_len, enc_len=enc_len)
    if cfg.encdec:
        frames = jnp.zeros((2, enc_len, cfg.d_model), jnp.float32)
        cache = model.prefill_cross(params, cache, frames)
    tok = jnp.zeros((2, 1), jnp.int32)
    metrics.reset_metrics()
    step = jax.jit(model.decode_step)
    logits, cache = step(params, cache, tok, jnp.int32(0))
    logits2, cache = step(params, cache, tok, jnp.int32(1))
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()
    reg = metrics.get_metrics()
    got = {k: reg.counter(f"decode/cache_write.{k}").value
           for k in ("token", "state")}
    assert got == _expected_cache_writes(model, cache)


def test_decode_matches_forward_dense():
    """Greedy decode logits must match teacher-forced forward logits."""
    cfg = _reduced("tinyllama-1.1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8)),
        jnp.int32,
    )
    logits_fwd, _ = model.forward(params, {"tokens": toks})
    cache = model.init_cache(1, 16)
    step = jax.jit(model.decode_step)
    for i in range(8):
        logits_dec, cache = step(
            params, cache, toks[:, i : i + 1], jnp.int32(i)
        )
        np.testing.assert_allclose(
            np.asarray(logits_dec[0, 0], np.float32),
            np.asarray(logits_fwd[0, i], np.float32),
            rtol=2e-3, atol=2e-3,
        )


def test_decode_matches_forward_ssm():
    """Recurrent-state decode must equal the parallel scan (xLSTM)."""
    cfg = _reduced("xlstm-1.3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 6)),
        jnp.int32,
    )
    logits_fwd, _ = model.forward(params, {"tokens": toks})
    cache = model.init_cache(1, 8)
    step = jax.jit(model.decode_step)
    for i in range(6):
        logits_dec, cache = step(
            params, cache, toks[:, i : i + 1], jnp.int32(i)
        )
        np.testing.assert_allclose(
            np.asarray(logits_dec[0, 0], np.float32),
            np.asarray(logits_fwd[0, i], np.float32),
            rtol=5e-3, atol=5e-3,
        )


def test_sliding_window_limits_context():
    cfg = dataclasses.replace(
        _reduced("tinyllama-1.1b"), sliding_window=4
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (1, 12)),
        jnp.int32,
    )
    logits, _ = model.forward(params, {"tokens": toks})
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # ring cache: decode with a window-4 cache buffer
    cache = model.init_cache(1, 12)
    # cache[0] = layer-0 dict, leaves stacked over periods:
    # (n_periods, ring_len, KV, B, hd)
    assert cache[0]["k"].shape[1] == 4  # ring sized to the window


def _decode_case(case):
    """(config, sequence length, cache length) of a decode-vs-forward
    case: a sliding window whose ring wraps, MLA's latent cache, attention
    beside Mamba states, and cross-attention over an encoder's K/V."""
    if case == "ring":
        cfg = dataclasses.replace(
            _reduced("tinyllama-1.1b"), sliding_window=4
        )
        return cfg, 12, 12
    if case == "mla":
        return dataclasses.replace(
            _reduced("deepseek-v2-lite-16b"), moe=None
        ), 8, 16
    if case == "hybrid":
        return dataclasses.replace(
            _reduced("jamba-1.5-large-398b"), moe=None
        ), 8, 16
    return _reduced("seamless-m4t-large-v2"), 8, 16


@pytest.mark.parametrize("case", ["ring", "mla", "hybrid", "encdec"])
def test_decode_matches_forward_cache_kinds(case):
    """Decode, writing each token into the cache in place, gives the
    teacher-forced forward pass's logits for every kind of cache."""
    cfg, n, cache_len = _decode_case(case)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (2, n)),
        jnp.int32,
    )
    batch = {"tokens": toks}
    cache = model.init_cache(2, cache_len, enc_len=8 if cfg.encdec else 0)
    if cfg.encdec:
        batch["enc_frames"] = jnp.asarray(
            np.random.default_rng(4).standard_normal((2, 8, cfg.d_model)),
            jnp.float32,
        )
        cache = model.prefill_cross(params, cache, batch["enc_frames"])
    logits_fwd, _ = model.forward(params, batch)
    step = jax.jit(model.decode_step)
    for i in range(n):
        logits_dec, cache = step(
            params, cache, toks[:, i : i + 1], jnp.int32(i)
        )
        np.testing.assert_allclose(
            np.asarray(logits_dec[:, 0], np.float32),
            np.asarray(logits_fwd[:, i], np.float32),
            rtol=2e-3, atol=2e-3,
        )
