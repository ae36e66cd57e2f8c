"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV activations are compressed into a low-rank latent ``c_kv`` of
``kv_lora_rank`` dims, RMS-normed (DeepSeek's ``kv_a_layernorm``), plus a
single shared RoPE key head; the decode cache stores only the normed
latent and the roped key — the architecture's whole point — and
up-projects per step.  Training/prefill materializes per-head K/V from the
latent (mathematically identical).

RoPE runs at the model's ``rope_theta``, with YaRN's frequencies and
softmax scale where the config sets ``rope_scaling``, as DeepSeek-V2's
published modelling code does.  Its rotary layout is rotate-half; the
published weights' interleaved layout is a fixed permutation of the
rope columns of ``wq`` and ``w_kr``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MLAConfig
from repro.models import layers
from repro.models.layers import apply_rope, blockwise_attention, cache_attention
from repro.parallel.sharding import BATCH_AXES, MODEL_AXIS, constrain


def mla_init(rng, d_model: int, num_heads: int, cfg: MLAConfig, dtype):
    r = jax.random.split(rng, 6)
    qk_head = cfg.nope_head_dim + cfg.rope_head_dim
    return {
        # Q: full-rank (V2-Lite has no Q compression)
        "wq": layers.dense_init(r[0], d_model, num_heads * qk_head, dtype),
        # KV latent down-projection, its RMSNorm, + shared rope key
        "w_dkv": layers.dense_init(r[1], d_model, cfg.kv_lora_rank, dtype),
        "kv_norm": layers.norm_init(cfg.kv_lora_rank, "rmsnorm", dtype),
        "w_kr": layers.dense_init(r[2], d_model, cfg.rope_head_dim, dtype),
        # latent -> per-head K(nope), V
        "w_uk": layers.dense_init(
            r[3], cfg.kv_lora_rank, num_heads * cfg.nope_head_dim, dtype
        ),
        "w_uv": layers.dense_init(
            r[4], cfg.kv_lora_rank, num_heads * cfg.v_head_dim, dtype
        ),
        "wo": layers.dense_init(
            r[5], num_heads * cfg.v_head_dim, d_model, dtype
        ),
    }


def mla_param_specs():
    return {
        "wq": P(None, MODEL_AXIS),
        "w_dkv": P(None, None),
        "kv_norm": {"scale": P(None)},
        "w_kr": P(None, None),
        "w_uk": P(None, MODEL_AXIS),
        "w_uv": P(None, MODEL_AXIS),
        "wo": P(MODEL_AXIS, None),
    }


def softmax_scale(cfg: MLAConfig) -> float:
    """The scores' scale: 1 / sqrt(q head), times YaRN's mscale_all_dim
    factor squared where the config scales RoPE."""
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= layers.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg: MLAConfig, theta: float):
    y = cfg.rope_scaling
    if y is None:
        return apply_rope(x, positions, theta)
    return apply_rope(
        x, positions, theta,
        freqs=layers.yarn_frequencies(cfg.rope_head_dim, theta, y),
        mscale=(layers.yarn_mscale(y.factor, y.mscale)
                / layers.yarn_mscale(y.factor, y.mscale_all_dim)),
    )


def _project(params, x, num_heads: int, cfg: MLAConfig, positions,
             theta: float):
    """(q, normed latent, roped shared key) of ``x``: (B, S, d)."""
    b, s, _ = x.shape
    qk_head = cfg.nope_head_dim + cfg.rope_head_dim
    with jax.named_scope("q"):
        q = (x @ params["wq"]).reshape(b, s, num_heads, qk_head)
        q_nope, q_rope = jnp.split(q, [cfg.nope_head_dim], axis=-1)
        q = jnp.concatenate(
            [q_nope, _rope(q_rope, positions, cfg, theta)], -1
        )
    with jax.named_scope("latent"):
        c_kv = layers.apply_norm(
            params["kv_norm"], x @ params["w_dkv"], "rmsnorm"
        )  # (B, S, r)
        k_rope = _rope(
            (x @ params["w_kr"]).reshape(b, s, 1, cfg.rope_head_dim),
            positions, cfg, theta,
        )
    return q, c_kv, k_rope


def _expand_kv(params, c_kv, k_rope, num_heads: int, cfg: MLAConfig):
    b, s, _ = c_kv.shape
    k_nope = (c_kv @ params["w_uk"]).reshape(
        b, s, num_heads, cfg.nope_head_dim
    )
    v = (c_kv @ params["w_uv"]).reshape(b, s, num_heads, cfg.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, num_heads, cfg.rope_head_dim))],
        -1,
    )
    return k, v


def mla_apply(
    params,
    x: jax.Array,
    num_heads: int,
    cfg: MLAConfig,
    *,
    positions,
    rope_theta: float,
    window: Optional[int] = None,
) -> jax.Array:
    b, s, _ = x.shape
    with jax.named_scope("mla"):
        q, c_kv, k_rope = _project(
            params, x, num_heads, cfg, positions, rope_theta
        )
        with jax.named_scope("expand"):
            k, v = _expand_kv(params, c_kv, k_rope, num_heads, cfg)
        with jax.named_scope("core"):
            q = constrain(q, BATCH_AXES, None, MODEL_AXIS, None)
            k = constrain(k, BATCH_AXES, None, MODEL_AXIS, None)
            out = blockwise_attention(
                q, k, v, causal=True, window=window,
                scale=softmax_scale(cfg),
            )
        with jax.named_scope("o"):
            y = out.reshape(b, s, num_heads * cfg.v_head_dim) @ params["wo"]
            return constrain(y, BATCH_AXES, None, None)


def mla_init_cache(batch: int, seq: int, cfg: MLAConfig, dtype):
    """The MLA cache: latent + shared rope key only (its memory win)."""
    return {
        "c_kv": jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, seq, 1, cfg.rope_head_dim), dtype),
    }


def mla_decode(
    params,
    x: jax.Array,
    cache: dict,
    layer,
    pos,
    num_heads: int,
    cfg: MLAConfig,
    *,
    rope_theta: float,
):
    """One-token decode. cache: {"c_kv", "k_rope"} stacked over layers;
    ``layer`` indexes this layer's entry, into which this token's normed
    latent and roped key are written."""
    b = x.shape[0]
    posv = jnp.full((b, 1), pos)
    with jax.named_scope("mla"):
        q, c_kv_new, k_rope_new = _project(
            params, x, num_heads, cfg, posv, rope_theta
        )
        with jax.named_scope("cache"):
            cache = {
                "c_kv": layers.write_token(
                    cache["c_kv"], layer, 1, pos, c_kv_new
                ),
                "k_rope": layers.write_token(
                    cache["k_rope"], layer, 1, pos, k_rope_new
                ),
            }
        # Up-project the whole latent cache for this step's attention
        # (the recompute trade MLA makes for its 1/~10x cache size).
        with jax.named_scope("expand"):
            k, v = _expand_kv(
                params, layers.cache_layer(cache["c_kv"], layer),
                layers.cache_layer(cache["k_rope"], layer), num_heads, cfg,
            )
        with jax.named_scope("core"):
            out = cache_attention(
                q, layers.to_cache_order(k), layers.to_cache_order(v),
                valid_len=pos + 1, scale=softmax_scale(cfg),
            )
        with jax.named_scope("o"):
            y = out.reshape(b, 1, num_heads * cfg.v_head_dim) @ params["wo"]
    return y, cache
