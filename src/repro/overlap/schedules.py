"""Executable FiCCO schedules as JAX collectives (shard_map bodies).

Every function runs *inside* a ``jax.shard_map`` over one mesh axis (the
tensor-parallel group) and implements the data-dependent pattern of paper
Fig. 3: the activation ``x`` arrives row (M) sharded, the weight ``w`` is
column (N) sharded and resident, and the output is the full gathered-M times
local-N block:

    out[d] = all_gather_M(x) @ w[d]            # (M, N_local)

The schedules differ in *how* the all-gather is decomposed and interleaved
with the GEMM:

  * ``serial_ag_matmul``     — baseline: one AG, one GEMM (paper Fig. 3b).
  * ``shard_p2p_matmul``     — AsyncTP-style ring: shards stream peer-to-peer
    (``lax.ppermute``), GEMM per shard (paper Fig. 3c).
  * ``ficco_*``              — FiCCO: each shard is split into ``g`` chunks;
    each step performs a *simultaneous all-to-all-shaped* exchange (one
    chunk to every peer — expressed as a chunk-sized ``lax.all_gather``)
    and the configured chunk-granular GEMM (paper Fig. 4c / Fig. 11b).

TPU DMA-offload note: XLA lowers these collectives to asynchronous
ICI transfers executed by the chips' DMA engines (collective-start /
collective-done pairs that the latency-hiding scheduler overlaps with the
interleaved matmuls), so "offload communication to GPU DMA engines" is the
*default honest execution mode* here — there is no core-driven RCCL analogue
on TPU.  The Pallas kernels in ``repro.kernels`` make the same pipeline
explicit with ``pltpu.make_async_remote_copy``.

All functions are numerically exact (no approximation): every schedule must
produce bit-identical row content to ``serial_ag_matmul`` up to dot-product
reassociation in the 2D (K-chunked) schedule.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.schedule_types import Schedule


def _my_index(axis_name: str):
    return lax.axis_index(axis_name)


# Stable scopes in the compiled program's metadata, so that a profile
# attributes each device op to a schedule's communication or its GEMMs.
def _exchange():
    return jax.named_scope("ficco/exchange")


def _gemm():
    return jax.named_scope("ficco/gemm")


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------

def serial_ag_matmul(x: jax.Array, w: jax.Array, *, axis_name: str) -> jax.Array:
    """Paper Fig. 3(b): all-gather the input shards, then one big GEMM."""
    with _exchange():
        x_full = lax.all_gather(x, axis_name, axis=0, tiled=True)  # (M, K)
    with _gemm():
        return x_full @ w


def shard_p2p_matmul(
    x: jax.Array, w: jax.Array, *, axis_name: str
) -> jax.Array:
    """Shard-granularity ring overlap (PyTorch AsyncTP, paper Fig. 3c).

    Each step sends the current shard to the right neighbour
    (``lax.ppermute`` — a single P2P link per step, the topology weakness
    FiCCO fixes) while computing the GEMM on the shard already held.
    """
    g = lax.axis_size(axis_name)
    me = _my_index(axis_name)
    m_s, _ = x.shape
    n_local = w.shape[1]
    out = jnp.zeros((g * m_s, n_local), dtype=jnp.result_type(x, w))
    perm = [(i, (i + 1) % g) for i in range(g)]

    buf = x
    for step in range(g):
        src = (me - step) % g  # whose shard we currently hold
        with _gemm():
            piece = (buf @ w).astype(out.dtype)
        out = lax.dynamic_update_slice(out, piece, (src * m_s, 0))
        if step != g - 1:
            with _exchange():
                buf = lax.ppermute(buf, axis_name, perm)
    return out


# ---------------------------------------------------------------------------
# FiCCO schedules (paper Fig. 11b)
# ---------------------------------------------------------------------------

def _chunk_rows(x: jax.Array, g: int) -> jax.Array:
    """(m_s, K) -> (g, m_c, K) row chunks: one per overlap step."""
    m_s, k = x.shape
    if m_s % g:
        raise ValueError(f"shard rows {m_s} not divisible by group {g}")
    return x.reshape(g, m_s // g, k)


def ficco_uniform_fused_1d(
    x: jax.Array, w: jax.Array, *, axis_name: str
) -> jax.Array:
    """uniform-fused-1D: g steps; step s exchanges chunk s with all peers
    (all-to-all shaped), Gathers local+remote into one buffer, runs ONE
    identical (M/g, N_local, K) GEMM, and Scatters the output rows."""
    g = lax.axis_size(axis_name)
    m_s, k = x.shape
    n_local = w.shape[1]
    m_c = m_s // g
    chunks = _chunk_rows(x, g)  # (g, m_c, K)
    out = jnp.zeros((g * m_s, n_local), dtype=jnp.result_type(x, w))
    for s in range(g):
        # One chunk to every peer, one chunk from every peer: the paper's
        # simultaneous all-to-all step (all links busy on a direct topology).
        with _exchange():
            gathered = lax.all_gather(chunks[s], axis_name, axis=0)
        step_buf = gathered.reshape(g * m_c, k)  # Gather (g, m_c, K)
        with _gemm():
            step_out = step_buf @ w  # identical GEMM every step
        # Scatter: row block from device d lands at global row d*m_s + s*m_c.
        step_out = step_out.reshape(g, m_c, n_local)
        for d in range(g):
            out = lax.dynamic_update_slice(
                out,
                step_out[d].astype(out.dtype),
                (d * m_s + s * m_c, 0),
            )
    return out


def ficco_hetero_fused_1d(
    x: jax.Array, w: jax.Array, *, axis_name: str
) -> jax.Array:
    """hetero-fused-1D: compute the whole local shard immediately (hiding
    the first exposed exchange), then per step one fused GEMM over the g-1
    *remote* chunks received in that step."""
    g = lax.axis_size(axis_name)
    me = _my_index(axis_name)
    m_s, k = x.shape
    n_local = w.shape[1]
    m_c = m_s // g
    out = jnp.zeros((g * m_s, n_local), dtype=jnp.result_type(x, w))

    # Step 0: local shard, no communication dependency.
    with _gemm():
        local = (x @ w).astype(out.dtype)
    out = lax.dynamic_update_slice(out, local, (me * m_s, 0))

    chunks = _chunk_rows(x, g)
    for s in range(g):
        with _exchange():
            gathered = lax.all_gather(chunks[s], axis_name, axis=0)
        # Remote-only gather: rotate so our own chunk is last, drop it.
        rolled = jnp.roll(gathered, -(me + 1), axis=0)[: g - 1]
        step_buf = rolled.reshape((g - 1) * m_c, k)
        with _gemm():
            step_out = (step_buf @ w).reshape(g - 1, m_c, n_local)
        for j in range(g - 1):
            src = (me + 1 + j) % g
            out = lax.dynamic_update_slice(
                out,
                step_out[j].astype(out.dtype),
                (src * m_s + s * m_c, 0),
            )
    return out


def ficco_hetero_unfused_1d(
    x: jax.Array, w: jax.Array, *, axis_name: str
) -> jax.Array:
    """hetero-unfused-1D: like hetero-fused but one GEMM *per chunk* —
    no Gather at all, maximum scheduling freedom, highest DIL."""
    g = lax.axis_size(axis_name)
    me = _my_index(axis_name)
    m_s, k = x.shape
    n_local = w.shape[1]
    m_c = m_s // g
    out = jnp.zeros((g * m_s, n_local), dtype=jnp.result_type(x, w))
    with _gemm():
        local = (x @ w).astype(out.dtype)
    out = lax.dynamic_update_slice(out, local, (me * m_s, 0))
    chunks = _chunk_rows(x, g)
    for s in range(g):
        with _exchange():
            gathered = lax.all_gather(chunks[s], axis_name, axis=0)
        rolled = jnp.roll(gathered, -(me + 1), axis=0)
        for j in range(g - 1):
            src = (me + 1 + j) % g
            with _gemm():
                piece = rolled[j] @ w  # (m_c, N_local): unfused chunk GEMM
            out = lax.dynamic_update_slice(
                out, piece.astype(out.dtype), (src * m_s + s * m_c, 0)
            )
    return out


def ficco_uniform_fused_2d(
    x: jax.Array, w: jax.Array, *, axis_name: str
) -> jax.Array:
    """uniform-fused-2D: chunks are K (column) slices; step s assembles the
    full-M (M, K/g) panel and runs an accumulating GEMM C += panel @ w_slice.
    Output rows are contiguous — no Scatter; requires accumulation instead.
    """
    g = lax.axis_size(axis_name)
    m_s, k = x.shape
    n_local = w.shape[1]
    if k % g:
        raise ValueError(f"K={k} not divisible by group {g}")
    k_c = k // g
    acc = jnp.zeros((g * m_s, n_local), dtype=jnp.float32)
    for s in range(g):
        chunk = lax.dynamic_slice(x, (0, s * k_c), (m_s, k_c))  # (m_s, K/g)
        with _exchange():
            gathered = lax.all_gather(chunk, axis_name, axis=0)
        panel = gathered.reshape(g * m_s, k_c)  # Gather (g, m_s, K/g)
        w_slice = lax.dynamic_slice(w, (s * k_c, 0), (k_c, n_local))
        with _gemm():
            acc = acc + (panel @ w_slice).astype(jnp.float32)  # C += A_s B_s
    return acc.astype(jnp.result_type(x, w))


SCHEDULE_FNS: dict[Schedule, Callable[..., jax.Array]] = {
    Schedule.SERIAL: serial_ag_matmul,
    Schedule.SHARD_P2P: shard_p2p_matmul,
    Schedule.UNIFORM_FUSED_1D: ficco_uniform_fused_1d,
    Schedule.HETERO_FUSED_1D: ficco_hetero_fused_1d,
    Schedule.HETERO_UNFUSED_1D: ficco_hetero_unfused_1d,
    Schedule.UNIFORM_FUSED_2D: ficco_uniform_fused_2d,
}


def run_schedule(
    schedule: Schedule,
    x: jax.Array,
    w: jax.Array,
    *,
    axis_name: str,
) -> jax.Array:
    return SCHEDULE_FNS[schedule](x, w, axis_name=axis_name)
