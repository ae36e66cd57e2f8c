"""FiCCO chunk exchange on TPU ICI DMA engines (Pallas).

This is the paper's "offload communication to GPU DMA engines" adapted to
TPU: one FiCCO step's *simultaneous all-to-all* — every device pushes its
current chunk to every peer — implemented with
``pltpu.make_async_remote_copy``.  No compute core (MXU/VPU) cycles move
bytes; the per-chip DMA engines drive the ICI links directly, the TPU
analogue of ``hipMemcpyDtoDAsync`` on a side stream (and the reason the
paper's *compute interference* term vanishes by construction on TPU).

The kernel is the communication half of the FiCCO schedules; the GEMMs stay
ordinary XLA/MXU matmuls — mirroring the paper's design rule of *not*
modifying the optimized GEMM library ("we make no changes to the existing
GEMM kernels").  ``ficco_ag_matmul.py`` additionally provides the fused
beyond-paper variant where DMA and MXU pipeline inside one kernel.

Validated on CPU with the Mosaic TPU interpreter
(``pltpu.InterpretParams``), which simulates cross-device DMAs faithfully.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Each communicating kernel owns a distinct collective id: it names the
# barrier semaphore the kernel handshakes on (``peer_barrier``).
EXCHANGE_COLLECTIVE_ID = 0


def interpret_params(interpret: bool):
    """``interpret=`` for a DMA kernel: the Mosaic TPU interpreter, which
    simulates cross-device DMAs and semaphores, or compiled Mosaic."""
    return pltpu.InterpretParams() if interpret else False


def peer_barrier(me, group: int, axis_name: str) -> None:
    """Handshake with every peer on ``axis_name`` before any remote write.

    A remote copy lands in the peer's output buffer, which XLA may still
    be using for an earlier op until the peer has entered this kernel.
    Each device signals the barrier semaphore of all g-1 peers, then
    waits for their g-1 signals, leaving the semaphore at zero.
    """
    sem = pltpu.get_barrier_semaphore()
    for i in range(1, group):
        pltpu.semaphore_signal(
            sem, 1, device_id={axis_name: lax.rem(me + i, group)}
        )
    pltpu.semaphore_wait(sem, group - 1)


def _exchange_kernel(
    group: int,
    axis_name: str,
    reverse: bool,
    chunk_ref,
    out_ref,
    send_sems,
    recv_sems,
):
    """Push ``chunk_ref`` to slot ``my_id`` of every peer's ``out_ref``.

    Slot layout: out[src] = chunk that device ``src`` held, so after the
    barrier every device owns the identical (g, m_c, K) gathered buffer.
    Traffic is fully symmetric: g-1 egress and g-1 ingress DMAs per device,
    saturating every ICI link of the axis — the paper's full-mesh argument.

    ``reverse`` issues the egress DMAs to peers in descending offset
    order; every device uses the same order, so each (sender, receiver,
    semaphore index) pairing stays unique and results are unchanged.
    """
    me = lax.axis_index(axis_name)

    # Local slot: plain on-device DMA (HBM -> HBM), no ICI traffic.
    local = pltpu.make_async_copy(
        chunk_ref, out_ref.at[me], recv_sems.at[group - 1]
    )
    local.start()
    peer_barrier(me, group, axis_name)

    copies = []
    for i in range(1, group):
        peer = lax.rem(me + (group - i if reverse else i), group)
        rc = pltpu.make_async_remote_copy(
            src_ref=chunk_ref,
            dst_ref=out_ref.at[me],
            send_sem=send_sems.at[i - 1],
            recv_sem=recv_sems.at[i - 1],
            device_id={axis_name: peer},
        )
        rc.start()
        copies.append(rc)

    # Wait: our g-1 sends drained, then the g-1 matching ingress DMAs
    # (peer j's copy into out[j] signals recv_sems[(me - j) % g - 1]).
    for rc in copies:
        rc.wait_send()
    for rc in copies:
        rc.wait_recv()
    local.wait()


def a2a_chunk_exchange(
    chunk: jax.Array,
    *,
    axis_name: str,
    group: int,
    interpret: bool = False,
    reverse: bool = False,
) -> jax.Array:
    """One FiCCO exchange step: (m_c, K) chunk -> (g, m_c, K) gathered.

    Must be called inside shard_map over ``axis_name`` with ``group``
    devices.  Equivalent to ``lax.all_gather(chunk, axis_name, axis=0)``
    but executed entirely by the ICI DMA engines from a single kernel.
    """
    kernel = functools.partial(_exchange_kernel, group, axis_name, reverse)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((group, *chunk.shape), chunk.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((group - 1,)),
            pltpu.SemaphoreType.DMA((group,)),
        ],
        interpret=interpret_params(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=EXCHANGE_COLLECTIVE_ID, has_side_effects=True
        ),
    )(chunk)


def ficco_uniform_fused_1d_dma(
    x: jax.Array,
    w: jax.Array,
    *,
    axis_name: str,
    interpret: bool = False,
    variant=None,
) -> jax.Array:
    """uniform-fused-1D with DMA-offloaded communication.

    Per step: Pallas DMA all-to-all of chunk ``s`` (communication), then a
    standard XLA GEMM on the gathered step buffer (compute) — library GEMMs
    untouched, exactly the paper's realization strategy (§VI-A).  XLA's
    scheduler overlaps step s+1's kernel DMAs with step s's matmul.

    ``variant`` (a :class:`repro.tune.KernelVariant`) picks the chunk
    count, the step-GEMM tile (routed through
    :func:`repro.kernels.chunked_gemm.chunked_matmul` with a full-K
    contraction, so row dots — and results — are unchanged), and the DMA
    dispatch order; ``None`` resolves the promoted default from
    :mod:`repro.tune.registry`.
    """
    g = lax.axis_size(axis_name)
    m_s, k = x.shape
    n_local = w.shape[1]
    if variant is None:
        from repro.tune.registry import resolve_variant

        variant = resolve_variant("dma_exchange", group=g)
    steps = int(variant.chunks)
    if m_s % steps:
        steps = g  # promoted cut doesn't divide this shard; classic cut
    m_c = m_s // steps
    reverse = variant.dispatch_order == "reverse"
    chunks = x.reshape(steps, m_c, k)
    rows = g * m_c
    # Tile the step GEMM only when the variant's blocks divide it evenly;
    # K stays un-blocked so each output row remains one full-K dot.
    blocked = (
        rows % variant.block_m == 0
        and n_local % variant.block_n == 0
        and (variant.block_m < rows or variant.block_n < n_local)
    )
    out = jnp.zeros((g * m_s, n_local), dtype=jnp.result_type(x, w))
    order = list(range(steps))
    if reverse:
        order.reverse()
    for s in order:
        gathered = a2a_chunk_exchange(
            chunks[s],
            axis_name=axis_name,
            group=g,
            interpret=interpret,
            reverse=reverse,
        )
        flat = gathered.reshape(rows, k)
        if blocked:
            from repro.kernels.chunked_gemm import chunked_matmul

            step_out = chunked_matmul(
                flat,
                w,
                block_m=variant.block_m,
                block_n=variant.block_n,
                block_k=k,
                interpret=interpret,
            ).reshape(g, m_c, n_local)
        else:
            step_out = (flat @ w).reshape(g, m_c, n_local)
        for d in range(g):
            out = lax.dynamic_update_slice(
                out, step_out[d].astype(out.dtype), (d * m_s + s * m_c, 0)
            )
    return out


__all__ = ["a2a_chunk_exchange", "ficco_uniform_fused_1d_dma"]
