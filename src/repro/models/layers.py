"""Shared neural layers: norms, RoPE, GQA attention, MLPs.

All layers are pure functions over param pytrees (no framework dep).  Every
linear that participates in tensor parallelism is annotated with sharding
constraints via ``repro.parallel.sharding.constrain`` so GSPMD shards it
over the ``model`` axis; the FiCCO overlap path replaces the AG->GEMM pairs
with explicit shard_map schedules (see parallel/tp.py).

Attention is doubly-blocked (scan over query blocks, scan over KV blocks
with online softmax) so 32k-token prefill fits per-device memory; the same
code handles full-causal and sliding-window masks.  Decode uses a KV cache
(ring buffer when a sliding window is configured) stacked over layers and
written one token at a time in place.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.obs import metrics as _metrics
from repro.parallel.sharding import BATCH_AXES, MODEL_AXIS, constrain


def dense_init(rng, in_dim: int, out_dim: int, dtype) -> jax.Array:
    std = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim)) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if kind == "nonparametric_ln":  # OLMo: no affine parameters
        return {}
    raise ValueError(kind)


def apply_norm(params, x: jax.Array, kind: str) -> jax.Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        if kind == "rmsnorm":
            y = xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, -1, keepdims=True) + 1e-6
            )
            return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        if kind == "layernorm":
            y = y * params["scale"].astype(jnp.float32) + params[
                "bias"
            ].astype(jnp.float32)
        return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor, ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(head_dim: int, theta: float,
                          scaling) -> tuple[int, int]:
    """The frequency indices between which YaRN ramps from the plain to
    the interpolated frequency: a dimension that turns ``n`` times over
    the original context sits at ``d ln(L / 2 pi n) / (2 ln theta)``."""
    def dim(turns):
        return (head_dim * math.log(
            scaling.original_max_position / (turns * 2 * math.pi))
            / (2 * math.log(theta)))

    low = max(math.floor(dim(scaling.beta_fast)), 0)
    high = min(math.ceil(dim(scaling.beta_slow)), head_dim - 1)
    return low, high


def yarn_frequencies(head_dim: int, theta: float, scaling) -> jax.Array:
    """YaRN's inverse frequencies: the plain ones below ``low``, those
    divided by ``factor`` above ``high``, a linear ramp between."""
    low, high = yarn_correction_range(head_dim, theta, scaling)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0,
    )
    plain = rope_frequencies(head_dim, theta)
    return plain * (1.0 - ramp) + plain / scaling.factor * ramp


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               mscale: float = 1.0) -> jax.Array:
    """x: (..., S, H, D) rotated pairwise; positions: (..., S).  ``freqs``
    replaces the plain inverse frequencies of ``theta`` (YaRN's), and
    ``mscale`` scales cos and sin."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (blockwise online-softmax; full-causal or sliding window)
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale=None):
    """q: (B,bq,H,Dk); k: (B,bk,KV,Dk); v: (B,bk,KV,Dv); mask: (bq,bk).
    Scores are scaled by ``scale``, else by 1 / sqrt(Dk)."""
    b, bq, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    kr = jnp.repeat(k, rep, axis=2)  # (B,bk,H,Dk)
    vr = jnp.repeat(v, rep, axis=2)  # (B,bk,H,Dv)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32)
    )
    scores = scores / math.sqrt(d) if scale is None else scores * scale
    scores = jnp.where(mask[None, None], scores, _NEG_INF)
    m = jnp.max(scores, -1)  # (B,H,bq)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, -1)  # (B,H,bq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return m, l, o


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Memory-O(S*block) exact attention.

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  ``q_offset`` is the absolute
    position of q[0] relative to k[0] (cross-attention uses causal=False).
    Scores are scaled by ``scale``, else by 1 / sqrt(D).
    """
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # pad to block multiples
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k

    q_blocks = qp.reshape(b, nq, block_q, h, d).transpose(1, 0, 2, 3, 4)
    k_blocks = kp.reshape(b, nk, block_k, k.shape[2], d).transpose(
        1, 0, 2, 3, 4
    )
    v_blocks = vp.reshape(b, nk, block_k, v.shape[2], dv).transpose(
        1, 0, 2, 3, 4
    )

    q_pos_base = jnp.arange(block_q)
    k_pos_base = jnp.arange(block_k)

    def q_step(_, qi_qblk):
        qi, qblk = qi_qblk
        q_pos = q_offset + qi * block_q + q_pos_base  # absolute

        def kv_step(carry, ki_kv):
            m_run, l_run, o_run = carry
            ki, kblk, vblk = ki_kv
            k_pos = ki * block_k + k_pos_base
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            mask &= (k_pos < sk)[None, :]
            mask &= ((q_offset + qi * block_q + q_pos_base) < q_offset + sq)[
                :, None
            ]
            m_b, l_b, o_b = _block_attn(qblk, kblk, vblk, mask, scale)
            m_new = jnp.maximum(m_run, m_b)
            a1 = jnp.exp(m_run - m_new)
            a2 = jnp.exp(m_b - m_new)
            l_new = l_run * a1 + l_b * a2
            o_new = (
                o_run * a1.transpose(0, 2, 1)[..., None]
                + o_b * a2.transpose(0, 2, 1)[..., None]
            )
            return (m_new, l_new, o_new), None

        m0 = jnp.full((b, h, block_q), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        o0 = jnp.zeros((b, block_q, h, dv), jnp.float32)
        (m_f, l_f, o_f), _ = lax.scan(
            kv_step, (m0, l0, o0), (jnp.arange(nk), k_blocks, v_blocks)
        )
        out = o_f / jnp.maximum(l_f, 1e-30).transpose(0, 2, 1)[..., None]
        return None, out

    _, outs = lax.scan(q_step, None, (jnp.arange(nq), q_blocks))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * block_q, h, dv)
    return out[:, :sq].astype(q.dtype)


# A layer's KV cache is (S, KV, B, D): slot, KV head, batch, head dim.
# The TPU compiler reads a decode step's cache in that order as stored,
# for one query head per KV head and for grouped ones (found by
# compiling the step for a v5e at head dim 128); a (B, S, KV, D) or
# (B, KV, S, D) cache it relays out, whole, every step.
KV_CACHE_BATCH_AXIS = 2


def kv_cache_shape(dims: AttnDims, batch: int, slots: int) -> tuple:
    """One layer's KV cache shape, (S, KV, B, D)."""
    return (slots, dims.num_kv_heads, batch, dims.head_dim)


def cache_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode attention over one layer's (S, KV, B, D) cache.

    q: (B, 1, H, D).  ``valid_len`` - number of leading slots that hold
    keys (scalar); None where every slot does (cross-attention).  Scores
    are scaled by ``scale``, else by 1 / sqrt(D).
    """
    b, _, h, d = q.shape
    s, kv = k_cache.shape[0], k_cache.shape[1]
    # each KV head serves h // kv query heads (GQA)
    qg = q.reshape(b, kv, h // kv, d)
    k_cache = constrain(
        k_cache, "data" if b == 1 else None, None, BATCH_AXES, None
    )
    scores = jnp.einsum(
        "bgrd,sgbd->bgrs", qg.astype(jnp.float32),
        k_cache.astype(jnp.float32),
    )
    scores = scores / math.sqrt(d) if scale is None else scores * scale
    if valid_len is not None:
        scores = jnp.where(jnp.arange(s) < valid_len, scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrs,sgbd->bgrd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


def to_cache_order(x: jax.Array) -> jax.Array:
    """(B, S, KV, D) keys or values, as projected, as (S, KV, B, D)."""
    return x.transpose(1, 2, 0, 3)


# ---------------------------------------------------------------------------
# Decode caches stacked over layers
# ---------------------------------------------------------------------------
# The decode step carries each cache leaf as one (layers, ...) buffer
# (donated by the serving engine).  A layer writes one token's row into
# it in place and reads its own layer where it lies; a recurrent state is
# replaced whole.  Each write counts its path at trace time
# (``decode/cache_write.token`` or ``decode/cache_write.state``).


def cache_layer(stack: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a cache leaf stacked over layers."""
    return lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def write_token(stack: jax.Array, layer, axis: int, slot,
                row: jax.Array) -> jax.Array:
    """``stack`` with one token's ``row`` (the layer's cache shape, 1 at
    ``axis``) written at ``slot`` of the layer's ``axis``."""
    _metrics.get_metrics().counter("decode/cache_write.token").inc()
    start = [0] * stack.ndim
    start[0], start[axis + 1] = layer, slot
    return lax.dynamic_update_slice(
        stack, row[None].astype(stack.dtype), start
    )


def write_state(stack: jax.Array, layer, state: jax.Array) -> jax.Array:
    """``stack`` with layer ``layer`` replaced by ``state``."""
    _metrics.get_metrics().counter("decode/cache_write.state").inc()
    return lax.dynamic_update_index_in_dim(
        stack, state.astype(stack.dtype), layer, 0
    )


# ---------------------------------------------------------------------------
# GQA attention layer (params + apply for train & decode)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int


def attn_init(rng, dims: AttnDims, dtype):
    r = jax.random.split(rng, 4)
    h, kv, hd, d = (
        dims.num_heads, dims.num_kv_heads, dims.head_dim, dims.d_model
    )
    return {
        "wq": dense_init(r[0], d, h * hd, dtype),
        "wk": dense_init(r[1], d, kv * hd, dtype),
        "wv": dense_init(r[2], d, kv * hd, dtype),
        "wo": dense_init(r[3], h * hd, d, dtype),
    }


def attn_param_specs():
    return {
        "wq": P(None, MODEL_AXIS),
        "wk": P(None, MODEL_AXIS),
        "wv": P(None, MODEL_AXIS),
        "wo": P(MODEL_AXIS, None),
    }


def attn_apply(
    params,
    x: jax.Array,
    dims: AttnDims,
    *,
    rope_theta: float,
    positions: jax.Array,
    window: Optional[int] = None,
    kv_for_cross: Optional[jax.Array] = None,
) -> jax.Array:
    """Training/prefill attention.  x: (B, S, d)."""
    b, s, _ = x.shape
    h, kv, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim
    src = kv_for_cross if kv_for_cross is not None else x
    kv_axis = MODEL_AXIS if kv > 1 else None
    with jax.named_scope("attn"):
        with jax.named_scope("qkv"):
            q = (x @ params["wq"]).reshape(b, s, h, hd)
            k = (src @ params["wk"]).reshape(b, src.shape[1], kv, hd)
            v = (src @ params["wv"]).reshape(b, src.shape[1], kv, hd)
            q = constrain(q, BATCH_AXES, None, MODEL_AXIS, None)
            k = constrain(k, BATCH_AXES, None, kv_axis, None)
            v = constrain(v, BATCH_AXES, None, kv_axis, None)
            if kv_for_cross is None:
                q = apply_rope(q, positions, rope_theta)
                k = apply_rope(k, positions, rope_theta)
        with jax.named_scope("core"):
            if kv_for_cross is None:
                out = blockwise_attention(q, k, v, causal=True, window=window)
            else:
                out = blockwise_attention(q, k, v, causal=False)
        with jax.named_scope("o"):
            y = out.reshape(b, s, h * hd) @ params["wo"]
            return constrain(y, BATCH_AXES, None, None)


def attn_decode(
    params,
    x: jax.Array,
    cache: dict,
    layer,
    pos: jax.Array,
    dims: AttnDims,
    *,
    rope_theta: float,
    window: Optional[int] = None,
):
    """One-token decode. x: (B, 1, d); cache: {"k","v"} stacked over
    layers, (L, S, KV, B, D); ``layer`` indexes this layer's entry.
    Returns (y, {"k","v"}) with this token's keys and values written."""
    from repro.parallel.context import get_overlap

    b, one, _ = x.shape
    h, kv, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("qkv"):
            q = (x @ params["wq"]).reshape(b, 1, h, hd)
            k = (x @ params["wk"]).reshape(b, 1, kv, hd)
            v = (x @ params["wv"]).reshape(b, 1, kv, hd)
            posv = jnp.full((b, 1), pos)
            q = apply_rope(q, posv, rope_theta)
            k = to_cache_order(apply_rope(k, posv, rope_theta))
            v = to_cache_order(v)

        ov = get_overlap()
        if (ov is not None
                and getattr(ov, "decode_attn", "gspmd") == "shard_map"):
            from repro.parallel import decode_attn

            k_layer = cache_layer(cache["k"], layer)
            if decode_attn.applicable(k_layer, window):
                with jax.named_scope("core"):
                    out, k_layer, v_layer = (
                        decode_attn.shard_map_attn_decode(
                            q, k, v, k_layer,
                            cache_layer(cache["v"], layer), pos,
                        )
                    )
                with jax.named_scope("kv_cache"):
                    # the time-sharded cache takes a masked write of the
                    # whole layer
                    new = {"k": write_state(cache["k"], layer, k_layer),
                           "v": write_state(cache["v"], layer, v_layer)}
                with jax.named_scope("o"):
                    y = out.reshape(b, 1, h * hd) @ params["wo"]
                return y, new

        with jax.named_scope("kv_cache"):
            s_cache = cache["k"].shape[1]
            slot = pos % s_cache if window is not None else pos
            new = {"k": write_token(cache["k"], layer, 0, slot, k),
                   "v": write_token(cache["v"], layer, 0, slot, v)}
        with jax.named_scope("core"):
            # a ring holds the last s_cache positions once it is full
            out = cache_attention(
                q, cache_layer(new["k"], layer), cache_layer(new["v"], layer),
                valid_len=jnp.minimum(pos + 1, s_cache),
            )
        with jax.named_scope("o"):
            y = out.reshape(b, 1, h * hd) @ params["wo"]
        return y, new


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(rng, d: int, ff: int, dtype, *, gated: bool = True):
    r = jax.random.split(rng, 3)
    p = {
        "w_up": dense_init(r[0], d, ff, dtype),
        "w_down": dense_init(r[1], ff, d, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(r[2], d, ff, dtype)
    return p


def mlp_param_specs(*, gated: bool = True):
    p = {"w_up": P(None, MODEL_AXIS), "w_down": P(MODEL_AXIS, None)}
    if gated:
        p["w_gate"] = P(None, MODEL_AXIS)
    return p


def mlp_apply(params, x: jax.Array) -> jax.Array:
    """TP MLP.  The up/gate projections are the paper's data-dependent
    AG->GEMM pair: with an overlap context active they run a bespoke
    FiCCO schedule (repro.parallel.tp); otherwise GSPMD serial.  The down
    projection's RS-side is left to XLA (the paper omits reduction-fused
    scenarios: DMA engines lack arithmetic, §IV-B2)."""
    from repro.parallel.context import get_overlap

    ov = get_overlap()
    with jax.named_scope("mlp"):
        with jax.named_scope("up_gate"):
            h = None
            if ov is not None and ov.mode != "gspmd_serial":
                from repro.parallel import tp

                if tp.overlap_applicable(x, params["w_up"]):
                    h = tp.tp_ficco_linear(x, params["w_up"], ov)
                    if "w_gate" in params:
                        g = tp.tp_ficco_linear(x, params["w_gate"], ov)
                        h = jax.nn.silu(g) * h
                    else:
                        h = jax.nn.gelu(h)
            if h is None:
                h = x @ params["w_up"]
                if "w_gate" in params:
                    h = jax.nn.silu(x @ params["w_gate"]) * h
                else:
                    h = jax.nn.gelu(h)
            h = constrain(h, BATCH_AXES, None, MODEL_AXIS)
        with jax.named_scope("down"):
            y = h @ params["w_down"]
            return constrain(y, BATCH_AXES, None, None)
