"""Fused FiCCO all-gather-matmul: DMA + MXU pipelined in ONE kernel.

Beyond-paper, TPU-native variant (DESIGN.md §2): instead of alternating a
communication kernel and a library GEMM (the paper's realization, kept in
``dma_exchange.py``), this kernel double-buffers the chunk exchange against
the step GEMM *inside* a single ``pallas_call``:

    step s:  start all-to-all DMAs for chunk s+1  (ICI DMA engines)
             wait chunk s's ingress DMAs
             MXU matmul on step-s gathered buffer -> output rows

The DMAs for step s+1 fly while the MXU multiplies step s — the contention
surface is only HBM bandwidth (the paper's residual CIL-memory term); there is no
kernel-launch gap, no gather kernel (chunks are DMA'd *into place* in the
step buffer), and no scatter kernel (the output rows are written directly).
This removes the Gather/Scatter streams that give uniform-fused-1D its HIGH
CIL signature — measured in EXPERIMENTS.md §Perf as the `dma_into_place`
optimization.

Layout: x shard (m_s, K) split into ``steps`` chunks of (m_c, K); the
(K, n_local) weight panel stays resident in VMEM for every step GEMM;
outputs are the (M = g*m_s, n_local) rows this device owns after the
gather.  The kernel requests its scoped VMEM explicitly
(``VMEM_LIMIT_BYTES``): the compiler's default limit is far below what
prefill-sized steps need.  ``repro.tune.prune`` budgets variants against
the same figure with the same footprint (``fused_vmem_bytes``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dma_exchange import interpret_params, peer_barrier

FUSED_COLLECTIVE_ID = 1
# Scoped VMEM the kernel requests: half of a v5e core's 128 MiB, four
# times the compiler's default limit.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def fused_steps(m_s: int, group: int, variant) -> tuple[int, int]:
    """(steps, depth) the fused kernel runs ``variant`` with on a shard of
    ``m_s`` rows: the variant's chunk count, or the classic g-way cut when
    that does not divide the shard."""
    steps = int(variant.chunks)
    if m_s % steps:
        steps = group
    depth = max(2, min(int(variant.buffer_depth), steps))
    return steps, depth


LANES = 128


def padded_cols(n_local: int) -> int:
    """Weight/output columns the kernel allocates: ``n_local`` rounded up
    to whole 128-lane tiles, since Mosaic slices and DMAs VMEM only on
    lane-aligned widths (TP=8 TinyLlama has n_local = 704)."""
    return -(-n_local // LANES) * LANES


def col_tile(n_cols: int) -> int:
    """Output columns one step-GEMM iteration produces: the widest
    lane-aligned tile that divides the padded width ``n_cols``."""
    for bn in (512, 256):
        if n_cols % bn == 0:
            return bn
    return LANES


def fused_vmem_bytes(
    group: int, m_c: int, k: int, n_local: int, depth: int, itemsize: int
) -> int:
    """VMEM one kernel instance allocates: ``depth`` inbound (g*m_c, K)
    step slots, the (K, n) weight panel, ``depth`` outbound (g*m_c, n)
    slots, and one f32 (g*m_c, col_tile) product, n = padded n_local."""
    rows = group * m_c
    n = padded_cols(n_local)
    return (
        itemsize * (depth * rows * k + k * n + depth * rows * n)
        + 4 * rows * col_tile(n)
    )


def _fused_kernel(
    group: int,
    axis_name: str,
    steps: int,
    depth: int,
    reverse: bool,
    m_c: int,
    n_local: int,
    x_ref,  # (steps, m_c, K) local chunks, ANY/HBM
    w_ref,  # (K, n_local), ANY/HBM
    o_ref,  # (steps, g*m_c, n_local): step s, rows of source d at d*m_c
    step_bufs,  # VMEM (depth, g*m_c, K): slot-buffered gathered steps
    w_vmem,  # VMEM (K, n_local)
    out_vmem,  # VMEM (depth, g*m_c, n_local): slot-buffered egress staging
    send_sems,  # DMA (depth, g-1)
    recv_sems,  # DMA (depth, g)
    out_sems,  # DMA (depth,): per-slot output egress
    ready_sems,  # REGULAR (depth,): receiver->sender slot flow control
):
    me = lax.axis_index(axis_name)
    bn = col_tile(n_local)

    # Dispatch order: which chunk each pipeline position carries.  Output
    # blocks are indexed by the chunk id, so reversing the issue order
    # changes overlap, not results.
    order = list(range(steps))
    if reverse:
        order.reverse()

    w_copy = pltpu.make_async_copy(w_ref, w_vmem, recv_sems.at[0, group - 1])
    w_copy.start()

    def start_step(s: int, slot: int, wait_slot: bool):
        """Send chunk s to all peers; receive into step_bufs[slot].

        Flow control: a slot is reused every ``depth`` steps.  Before
        pushing a position ``>= depth`` into a peer's slot we must have
        that peer's release signal from its consumption ``depth``
        positions earlier (g-1 signals total) — otherwise a fast sender
        can overwrite a buffer a slow receiver is still multiplying from
        (a data race the Mosaic interpreter's race detector reproduces if
        this wait is removed).
        """
        if wait_slot:
            pltpu.semaphore_wait(ready_sems.at[slot], group - 1)
        mine = step_bufs.at[slot, pl.ds(me * m_c, m_c)]
        local = pltpu.make_async_copy(
            x_ref.at[s], mine, recv_sems.at[slot, group - 1]
        )
        local.start()
        descs = [local]
        for i in range(1, group):
            rc = pltpu.make_async_remote_copy(
                src_ref=x_ref.at[s],
                dst_ref=mine,
                send_sem=send_sems.at[slot, i - 1],
                recv_sem=recv_sems.at[slot, i - 1],
                device_id={axis_name: lax.rem(me + i, group)},
            )
            rc.start()
            descs.append(rc)
        return descs

    def wait_step(descs):
        for rc in descs[1:]:
            rc.wait_send()
        for rc in descs[1:]:
            rc.wait_recv()
        descs[0].wait()

    def release_slot(slot: int):
        """Tell every peer our copy of this slot is consumed."""
        for i in range(1, group):
            pltpu.semaphore_signal(
                ready_sems.at[slot], 1,
                device_id={axis_name: lax.rem(me + i, group)},
            )

    def step_gemm(slot: int):
        """out_vmem[slot] = step_bufs[slot] @ w, one column tile per
        iteration so the kernel's code stays one tile's worth."""

        def tile(j, carry):
            col = pl.multiple_of(j * bn, bn)
            out_vmem[slot, :, pl.ds(col, bn)] = jnp.dot(
                step_bufs[slot],
                w_vmem[:, pl.ds(col, bn)],
                preferred_element_type=jnp.float32,
            ).astype(out_vmem.dtype)
            return carry

        lax.fori_loop(0, n_local // bn, tile, 0)

    w_copy.wait()
    peer_barrier(me, group, axis_name)
    inflight = start_step(order[0], 0, False)
    # Output egress is slot-buffered like the ingress: a position's
    # (g*m_c, n_local) block drains to HBM while later positions' exchange
    # and matmul proceed.  A slot is only rewritten after its previous
    # drain (``depth`` positions earlier) completed — without that wait a
    # fast MXU could clobber bytes the DMA engine is still reading.
    out_copies: list = [None] * depth
    for pos, s in enumerate(order):
        slot = pos % depth
        wait_step(inflight)
        # Kick off the next exchange, THEN multiply — so the next
        # position's DMAs fly while the MXU works on this one — and only
        # then release this slot to the peers that will refill it.
        if pos + 1 < steps:
            inflight = start_step(
                order[pos + 1], (pos + 1) % depth, pos + 1 >= depth
            )
        if out_copies[slot] is not None:
            out_copies[slot].wait()
        step_gemm(slot)
        if pos + depth < steps:
            release_slot(slot)
        out_copy = pltpu.make_async_copy(
            out_vmem.at[slot], o_ref.at[s], out_sems.at[slot]
        )
        out_copy.start()
        out_copies[slot] = out_copy
    for out_copy in out_copies:
        if out_copy is not None:
            out_copy.wait()


def ficco_ag_matmul_fused(
    x: jax.Array,
    w: jax.Array,
    *,
    axis_name: str,
    interpret: bool = False,
    variant=None,
) -> jax.Array:
    """Fused uniform-fused-1D: returns (M, n_local) like the reference.

    Call inside shard_map over ``axis_name``.  VMEM: ``fused_vmem_bytes``
    at the plan ``fused_steps`` picks must fit ``VMEM_LIMIT_BYTES``, which
    ``repro.tune.prune`` checks before a variant reaches the compiler.

    ``variant`` (a :class:`repro.tune.KernelVariant`) picks the chunk
    count, DMA buffer depth and dispatch order; ``None`` resolves the
    promoted default from :mod:`repro.tune.registry`.  Results are
    bit-identical across variants: each output row is one full-K dot.
    """
    g = lax.axis_size(axis_name)
    m_s, k = x.shape
    n_out = w.shape[1]
    n_local = padded_cols(n_out)
    if n_local != n_out:  # zero columns; the real ones are unchanged
        w = jnp.pad(w, ((0, 0), (0, n_local - n_out)))
    if variant is None:
        from repro.tune.registry import resolve_variant

        variant = resolve_variant("ficco_ag_matmul", group=g)
    steps, depth = fused_steps(m_s, g, variant)
    reverse = variant.dispatch_order == "reverse"
    m_c = m_s // steps
    chunks = x.reshape(steps, m_c, k)
    kernel = functools.partial(
        _fused_kernel, g, axis_name, steps, depth, reverse, m_c, n_local
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((steps, g * m_c, n_local), x.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((depth, g * m_c, k), x.dtype),
            pltpu.VMEM((k, n_local), w.dtype),
            pltpu.VMEM((depth, g * m_c, n_local), x.dtype),
            pltpu.SemaphoreType.DMA((depth, g - 1)),
            pltpu.SemaphoreType.DMA((depth, g)),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.REGULAR((depth,)),
        ],
        interpret=interpret_params(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=FUSED_COLLECTIVE_ID,
            has_side_effects=True,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(chunks, w)
    # out[s, d*m_c:] = rows of source d, step s -> global row d*m_s + s*m_c.
    out = out.reshape(steps, g, m_c, n_local).transpose(1, 0, 2, 3)
    return out.reshape(g * m_s, n_local)[:, :n_out]


__all__ = [
    "VMEM_LIMIT_BYTES",
    "col_tile",
    "ficco_ag_matmul_fused",
    "padded_cols",
    "fused_steps",
    "fused_vmem_bytes",
]
