"""DeepSeek-V2's mechanisms in the program: YaRN RoPE in latent attention,
a dropless expert layer that holds a share of the experts, and leading
dense layers before the scanned MoE stack; and every other registry
model's parameter and cache trees as they were before these came."""

import dataclasses
import hashlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig
from repro.models import layers, mla, moe
from repro.models.model import build_model
from repro.obs import metrics

# sha256 (first 16 hex digits) of each model's parameter and cache trees
# (paths, shapes and dtypes; a cache of batch 2 and 16 slots), reduced
# and whole, recorded on the tree before leading dense layers, YaRN and
# the held-expert layer were added.  DeepSeek-V2-Lite's entry was
# corrected then, and is left out.
TREES_BEFORE = {
    "arctic-480b/reduced": "74733d9902bccda0",
    "arctic-480b": "b35e1e70b2164bea",
    "internvl2-76b/reduced": "9f7087ca1c4dc0c8",
    "internvl2-76b": "c472c8dc10775ef3",
    "jamba-1.5-large-398b/reduced": "f77a21d2c46723e5",
    "jamba-1.5-large-398b": "eb77664fbe5c98f6",
    "olmo-1b/reduced": "0e6e3d43404c9058",
    "olmo-1b": "9c3bd850b43fa2e3",
    "seamless-m4t-large-v2/reduced": "ba31362a24554919",
    "seamless-m4t-large-v2": "f8cd5d8e9bdd746d",
    "smollm-360m/reduced": "2739caa36781120b",
    "smollm-360m": "d2d0a0b10a7ebcc8",
    "tinyllama-1.1b/reduced": "5f07a6787c4057dd",
    "tinyllama-1.1b": "cfb0b313a6897094",
    "xlstm-1.3b/reduced": "272a204e78801ec3",
    "xlstm-1.3b": "a06d0b26b64c9b21",
    "yi-9b/reduced": "5f07a6787c4057dd",
    "yi-9b": "761868f472a73164",
}


def _trees_digest(cfg) -> str:
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: model.init_cache(2, 16, enc_len=8 if cfg.encdec else 0))

    def desc(tree):
        return [(jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
                for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]]

    text = json.dumps([desc(params), desc(cache)])
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(TREES_BEFORE))
def test_models_without_lead_layers_keep_their_trees(name):
    arch, _, cut = name.partition("/")
    cfg = get_config(arch)
    assert not (cfg.moe and cfg.moe.first_dense_layers)
    assert not (cfg.mla and cfg.mla.rope_scaling)
    assert _trees_digest(cfg.reduced() if cut else cfg) == (
        TREES_BEFORE[name])


def test_deepseek_v2_lite_is_the_published_model():
    cfg = get_config("deepseek-v2-lite-16b")
    model = build_model(cfg)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads) == (27, 2048, 16)
    assert (cfg.d_ff, cfg.vocab_size, cfg.tie_embeddings) == (
        10944, 102400, False)
    m = cfg.mla
    assert (m.q_lora_rank, m.nope_head_dim, m.rope_head_dim,
            m.kv_lora_rank, m.v_head_dim) == (0, 128, 64, 512, 128)
    e = cfg.moe
    assert (e.num_experts, e.top_k, e.num_shared_experts, e.d_ff_expert,
            e.first_dense_layers, e.norm_topk_prob, e.capacity_factor,
            e.held) == (64, 6, 2, 1408, 1, False, None, (0, 64))
    # one dense layer, then 26 MoE layers scanned as periods of one
    assert (model.n_lead, model.n_periods) == (1, 26)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert params["lead"][0]["ffn"]["w_up"].shape == (1, 2048, 10944)
    assert params["layers"][0]["ffn"]["w_up"].shape == (26, 64, 2048, 1408)
    assert params["layers"][0]["ffn"]["shared"]["w_up"].shape == (
        26, 2048, 2816)
    # the cache holds the latent and the rope key: 576 values a token
    cache = jax.eval_shape(lambda: model.init_cache(4, 2048))
    values = sum(a.size for a in jax.tree.leaves(cache))
    assert values == 4 * 2048 * 27 * 576
    # reduced: one lead layer and two MoE periods, half the experts held
    small = build_model(cfg.reduced())
    assert (small.n_lead, small.n_periods) == (1, 2)


def test_yarn_frequencies_and_scale_are_deepseeks():
    """DeepSeek-V2-Lite: rope dim 64, base 10,000, factor 40 from 4,096
    positions, beta 32 / 1, mscale 0.707 and mscale_all_dim 0.707."""
    cfg = get_config("deepseek-v2-lite-16b").mla
    y = cfg.rope_scaling
    assert layers.yarn_correction_range(64, 10000.0, y) == (10, 23)
    inv = np.asarray(layers.yarn_frequencies(64, 10000.0, y))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, plain * (1 - ramp) + plain / 40 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    m = layers.yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert mla.softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    # cos and sin keep their size: mscale / mscale_all_dim = 1
    x = jnp.ones((1, 3, 1, 64))
    pos = jnp.arange(3)[None]
    np.testing.assert_array_equal(
        mla._rope(x, pos, cfg, 10000.0),
        layers.apply_rope(x, pos, 10000.0, freqs=jnp.asarray(inv)))
    # without scaling, RoPE and the scale are the plain ones
    plain_cfg = dataclasses.replace(cfg, rope_scaling=None)
    assert mla.softmax_scale(plain_cfg) == pytest.approx(192 ** -0.5)


def _moe_cfg(**kw):
    base = dict(num_experts=16, top_k=6, num_shared_experts=2,
                d_ff_expert=16, norm_topk_prob=False, capacity_factor=None)
    return MoEConfig(**{**base, **kw})


def _moe_params(cfg, seed=0, d=32):
    whole = dataclasses.replace(cfg, held_experts=None)
    p = moe.moe_init(jax.random.PRNGKey(seed), d, whole, jnp.float32)
    # a router that separates the experts well, so that no top-k tie
    # depends on the order of a sum
    p["router"] = p["router"] * 4.0
    return p


def _share(params, first, count):
    out = dict(params)
    for k in ("w_up", "w_gate", "w_down"):
        out[k] = params[k][first:first + count]
    return out


def _dense_moe(params, x, cfg):
    """The layer written out plainly: every token through every expert,
    weighted by its top-k router probabilities (renormalised or not as
    ``cfg`` says), plus the shared experts."""
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xf @ params["router"], -1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], top_e].set(top_w)
    h = jnp.einsum("td,edf->etf", xf, params["w_up"])
    g = jnp.einsum("td,edf->etf", xf, params["w_gate"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * h, params["w_down"])
    out = jnp.einsum("te,etd->td", gate, y).reshape(x.shape)
    if "shared" in params:
        out = out + layers.mlp_apply(params["shared"], x)
    return out


def test_held_shares_add_up_to_the_whole_layer():
    """Four layers each holding 4 of 16 experts, the shared experts
    counted once, give the layer that holds all 16."""
    cfg = _moe_cfg()
    params = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    whole, _ = moe.moe_apply(params, x, cfg)
    shared = layers.mlp_apply(params["shared"], x)
    parts = [moe.moe_apply(_share(params, f, 4), x,
                           dataclasses.replace(cfg, held_experts=(f, 4)))[0]
             - shared for f in range(0, 16, 4)]
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(whole, _dense_moe(params, x, cfg),
                               rtol=1e-5, atol=1e-5)
    # a share is its experts' part: not the whole, not nothing
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-2
    assert float(jnp.max(jnp.abs(parts[0] + shared - whole))) > 1e-2


def test_skewed_routing_drops_nothing():
    """Every token routed to expert 0 first: the dropless layer computes
    all of them, where the capacity path drops past its capacity."""
    cfg = _moe_cfg(top_k=2, held_experts=(0, 4))
    params = _moe_params(cfg)
    # a constant feature that the router reads for expert 0 alone
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    x = x.at[..., 0].set(8.0)
    params["router"] = params["router"].at[0, :].set(0.0).at[0, 0].set(4.0)
    probs = jax.nn.softmax(x.reshape(-1, 32) @ params["router"], -1)
    assert bool(jnp.all(jnp.argmax(probs, -1) == 0))
    mine = _share(params, 0, 4)
    got, _ = moe.moe_apply(mine, x, cfg)
    want = _dense_moe(dict(params, w_up=params["w_up"].at[4:].set(0)),
                      x, dataclasses.replace(cfg, held_experts=None))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    capped, _ = moe.moe_apply(params, x, dataclasses.replace(
        cfg, held_experts=None, capacity_factor=1.0))
    whole, _ = moe.moe_apply(params, x, dataclasses.replace(
        cfg, held_experts=None))
    assert float(jnp.max(jnp.abs(capped - whole))) > 1e-2


def test_topk_weights_are_not_renormalised():
    cfg = _moe_cfg(num_shared_experts=0)
    params = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 32))
    raw, _ = moe.moe_apply(params, x, cfg)
    np.testing.assert_allclose(raw, _dense_moe(params, x, cfg), rtol=1e-5,
                               atol=1e-5)
    normed, _ = moe.moe_apply(
        params, x, dataclasses.replace(cfg, norm_topk_prob=True))
    np.testing.assert_allclose(
        normed, _dense_moe(params, x, dataclasses.replace(
            cfg, norm_topk_prob=True)), rtol=1e-5, atol=1e-5)
    # top-6 of 16 holds well under all of the probability
    assert float(jnp.max(jnp.abs(normed - raw))) > 1e-2


def test_a_share_needs_the_dropless_path():
    cfg = _moe_cfg(capacity_factor=1.25, held_experts=(0, 4))
    params = _share(_moe_params(cfg), 0, 4)
    with pytest.raises(ValueError, match="dropless"):
        moe.moe_apply(params, jnp.zeros((1, 2, 32)), cfg)


@pytest.mark.parametrize("scan", [True, False])
def test_the_dropless_counter_counts_each_moe_layer(scan):
    """Counted at trace time: once for the scan's body, or once for each
    MoE layer unrolled; the dense first layer counts nothing."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(),
                              scan_layers=scan)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    metrics.reset_metrics()
    jax.jit(model.forward)(params, {"tokens": jnp.zeros((1, 4), jnp.int32)})
    reg = metrics.get_metrics()
    assert reg.counter("moe/dropless").value == (1 if scan else 2)
    assert reg.counter("moe/capacity").value == 0


def test_decode_with_a_lead_layer_matches_the_forward():
    """The dense first layer's cache and the MoE stack's, written in
    place each step, give the forward's logits at every position."""
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    toks = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 10)), jnp.int32)
    want, _ = model.forward(params, {"tokens": toks})
    cache = model.init_cache(2, 16)
    assert set(cache) == {"lead", "layers"}
    dims = model.cache_batch_dims(cache)
    assert dims == {"lead": [{"c_kv": 1, "k_rope": 1}],
                    "layers": [{"c_kv": 1, "k_rope": 1}]}
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    for i in range(toks.shape[1]):
        got, cache = step(params, cache, toks[:, i:i + 1], jnp.int32(i))
        np.testing.assert_allclose(got[:, 0], want[:, i], rtol=2e-3,
                                   atol=2e-3)


def test_model_param_specs_follow_the_lead_stack():
    model = build_model(get_config("deepseek-v2-lite-16b").reduced())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = model.param_specs()
    assert jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    ) == jax.tree.structure(params)
    assert specs["lead"][0]["attn"]["kv_norm"]["scale"] == (
        jax.sharding.PartitionSpec(None, None))
