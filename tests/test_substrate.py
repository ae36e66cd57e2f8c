"""Substrate tests: pipeline determinism, optimizer, checkpoint, training
convergence on the synthetic task, serve engine."""

import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import SyntheticLM, make_pipeline
from repro.models.model import build_model
from repro.serve.engine import DecodeEngine, Request, make_serve_step
from repro.train import optimizer as opt
from repro.train.loop import train
from repro.ckpt.checkpoint import restore_checkpoint, save_checkpoint

SHAPE = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")


def test_pipeline_deterministic():
    cfg = get_config("tinyllama-1.1b").reduced()
    a = SyntheticLM(cfg, SHAPE, seed=3).batch_at(7)
    b = SyntheticLM(cfg, SHAPE, seed=3).batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(cfg, SHAPE, seed=4).batch_at(7)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_prefetcher_yields():
    cfg = get_config("smollm-360m").reduced()
    it = iter(make_pipeline(cfg, SHAPE))
    b1 = next(it)
    b2 = next(it)
    assert b1["tokens"].shape == (4, 32)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b2["tokens"]))


def test_optimizer_descends_quadratic():
    ocfg = opt.OptimizerConfig(peak_lr=0.1, warmup_steps=1, decay_steps=100)
    params = {"w": jnp.ones((4,))}
    state = opt.init_state(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}  # d/dw w^2
        params, state, _ = opt.apply_updates(params, grads, state, ocfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.2


def test_lr_schedule_shape():
    ocfg = opt.OptimizerConfig(peak_lr=1.0, min_lr=0.1, warmup_steps=10,
                               decay_steps=100)
    lrs = [float(opt.lr_at(ocfg, jnp.int32(s))) for s in (0, 10, 100)]
    assert lrs[0] < 0.2 and abs(lrs[1] - 1.0) < 1e-5 and abs(
        lrs[2] - 0.1) < 1e-5


def test_training_reduces_loss():
    from repro.train.optimizer import OptimizerConfig

    cfg = get_config("smollm-360m").reduced()
    res = train(
        cfg, SHAPE, steps=40, log_every=100, log_fn=lambda *_: None,
        ocfg=OptimizerConfig(peak_lr=3e-3, warmup_steps=5, decay_steps=40),
    )
    first = res["history"][0]["loss"]
    last = res["history"][-1]["loss"]
    assert last < first - 0.3, (first, last)


def test_checkpoint_roundtrip():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt_state": opt.init_state(params)}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, 5)
        restored, step = restore_checkpoint(d, state)
        assert step == 5
        a = jax.tree.leaves(state)
        b = jax.tree.leaves(restored)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_serve_engine_greedy():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, batch_size=2, cache_len=64)
    reqs = [
        Request(np.asarray([1, 2, 3], np.int32), max_new_tokens=4),
        Request(np.asarray([5, 6], np.int32), max_new_tokens=4),
    ]
    out = eng.run(reqs)
    assert all(len(r.out) == 4 for r in out)
    assert all(0 <= t < cfg.vocab_size for r in out for t in r.out)


def test_serve_engine_donates_cache():
    """The engine's step donates its cache: the compiled step aliases
    every cache leaf to an output, the cache it was handed is gone after
    a step, and greedy output over several batches equals an undonated
    step's token for token."""
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, batch_size=2, cache_len=32)
    text = eng.step_fn.lower(
        params, eng.cache, jnp.zeros((2, 1), jnp.int32), jnp.int32(0)
    ).compile().as_text()
    header = text.split("entry_computation_layout")[0]
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    n_params = len(jax.tree.leaves(params))
    n_cache = len(jax.tree.leaves(eng.cache))
    assert aliased == set(range(n_params, n_params + n_cache))

    plain = DecodeEngine(cfg, params, batch_size=2, cache_len=32)
    plain.step_fn = jax.jit(make_serve_step(plain.model))
    rng = np.random.default_rng(5)
    for _ in range(3):
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (5, 3)]
        given = eng.cache
        got = eng.run([Request(p, max_new_tokens=4) for p in prompts])
        want = plain.run([Request(p, max_new_tokens=4) for p in prompts])
        assert all(a.is_deleted() for a in jax.tree.leaves(given))
        assert [r.out for r in got] == [r.out for r in want]


def test_grad_accumulation_equivalence():
    """accum_steps=4 must produce (numerically) the same update as the
    monolithic batch."""
    import jax.numpy as jnp

    from repro.models.model import build_model
    from repro.train.loop import init_train_state, make_train_step

    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    ocfg = opt.OptimizerConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=10)
    state = init_train_state(model, jax.random.PRNGKey(0))
    from repro.data.pipeline import SyntheticLM

    batch = jax.tree.map(jnp.asarray, SyntheticLM(cfg, SHAPE).batch_at(0))

    s1, m1 = jax.jit(make_train_step(model, ocfg))(state, batch)
    s4, m4 = jax.jit(make_train_step(model, ocfg, accum_steps=4))(
        state, batch
    )
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s4["params"])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-3, atol=2e-3,
        )
