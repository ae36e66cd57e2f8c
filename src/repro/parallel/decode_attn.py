"""Distributed decode attention: shard_map flash-decode over the cache.

§Perf pair-2 finding: with the KV cache time-sharded over the ``model``
axis, GSPMD materializes gathered K/V slices for every decode step
(~4.6 GB/step for yi-9b x decode_32k) because it partitions the
scores -> softmax -> AV chain op-by-op.  The fix is the same move FiCCO
makes for GEMMs: take the data-dependent pattern out of the implicit
partitioner and express it explicitly.

Each device holds a contiguous time-slice of the cache, performs the
in-place cache update if ``pos`` lands in its slice (masked write — shape
static), computes *partial* attention with local max/denominator, and the
group combines with one tiny pmax + two psums of (B, H)-sized statistics:

    m   = pmax_g(m_loc)
    l   = psum_g(l_loc * exp(m_loc - m))
    out = psum_g(o_loc * exp(m_loc - m)) / l

Collectives per layer drop from O(B * S * KV * hd) gathered bytes to
O(B * H * hd) — measured in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import BATCH_AXES, MODEL_AXIS, _active_mesh

_NEG_INF = -1e30


def applicable(k_cache: jax.Array, window) -> bool:
    mesh = _active_mesh()
    if mesh is None or MODEL_AXIS not in mesh.shape:
        return False
    g = mesh.shape[MODEL_AXIS]
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    dp = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    return (
        g > 1
        and window is None
        and k_cache.shape[1] % g == 0
        and k_cache.shape[1] >= 1024
        and k_cache.shape[0] % dp == 0
    )


def shard_map_attn_decode(
    q: jax.Array,  # (B, 1, H, D) — post-RoPE
    k_new: jax.Array,  # (B, 1, KV, D) — post-RoPE
    v_new: jax.Array,  # (B, 1, KV, D)
    k_cache: jax.Array,  # (B, S, KV, D), time-sharded over `model`
    v_cache: jax.Array,
    pos,  # scalar int32
):
    """Returns (out (B, 1, H, D), new_k_cache, new_v_cache)."""
    mesh = _active_mesh()
    g = mesh.shape[MODEL_AXIS]
    b, s, kv, d = k_cache.shape
    h = q.shape[2]
    s_loc = s // g
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    bspec = batch_axes if batch_axes else None

    def body(q, k_new, v_new, k_c, v_c, pos):
        me = lax.axis_index(MODEL_AXIS)
        offset = me * s_loc
        local_idx = jnp.arange(s_loc)
        # masked in-place write (shard-local; no cross-device traffic)
        write = (local_idx + offset == pos)[None, :, None, None]
        k_c = jnp.where(write, k_new.astype(k_c.dtype), k_c)
        v_c = jnp.where(write, v_new.astype(v_c.dtype), v_c)

        rep = h // kv
        kr = jnp.repeat(k_c, rep, axis=2)  # (B, s_loc, H, D)
        vr = jnp.repeat(v_c, rep, axis=2)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk",
            q.astype(jnp.float32),
            kr.astype(jnp.float32),
        ) / math.sqrt(d)
        valid = (local_idx + offset <= pos)[None, None, None, :]
        scores = jnp.where(valid, scores, _NEG_INF)
        m_loc = jnp.max(scores, -1)  # (B, H, 1)
        p = jnp.exp(scores - m_loc[..., None])
        p = jnp.where(valid, p, 0.0)
        l_loc = jnp.sum(p, -1)  # (B, H, 1)
        o_loc = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))

        m_g = lax.pmax(m_loc, MODEL_AXIS)
        corr = jnp.exp(m_loc - m_g)
        l_g = lax.psum(l_loc * corr, MODEL_AXIS)
        o_g = lax.psum(
            o_loc * corr.transpose(0, 2, 1)[..., None], MODEL_AXIS
        )
        out = (o_g / jnp.maximum(l_g, 1e-30).transpose(0, 2, 1)[..., None])
        return out.astype(q.dtype), k_c, v_c

    rep_spec = P(bspec, None, None, None)
    cache_spec = P(bspec, MODEL_AXIS, None, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep_spec, rep_spec, rep_spec, cache_spec, cache_spec,
                  P()),
        out_specs=(rep_spec, cache_spec, cache_spec),
        check_vma=False,
    )(q, k_new, v_new, k_cache, v_cache, pos)
