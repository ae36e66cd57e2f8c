"""Expert-parallel (EP) overlap: chunked all-to-all token dispatch.

The paper's EP scenarios (Table I g13–g16): input tokens are communicated
all-to-all before the expert FFN GEMMs run — a data-dependent comm->compute
pair.  FiCCO decomposes the dispatch one level deeper: the capacity
dimension is cut into ``g`` chunks, each chunk is exchanged and its expert
GEMM starts immediately, so expert compute overlaps the remaining dispatch.
This also hides A2A *asymmetry* (paper Fig. 5): a hot expert's extra tokens
arrive across several chunks whose compute is already pipelined.

Layout convention (GShard-style, grouped):
  x: (E_local * g_chunks ... ) — concretely each device holds tokens grouped
  by destination expert: (E, C, D) where E = global expert count, C =
  per-expert capacity from this device.  ``lax.all_to_all`` over the EP axis
  swaps the expert dimension for the source-device dimension, delivering
  (g, E_local, C, D) -> reshaped to (E_local, g*C, D) expert batches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax



def _ffn(x: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """One expert's FFN applied batched over local experts.

    x: (E_local, T, D); w_up: (E_local, D, F); w_down: (E_local, F, D).
    """
    h = jnp.einsum("etd,edf->etf", x, w_up)
    h = jax.nn.gelu(h)
    return jnp.einsum("etf,efd->etd", h, w_down)


def serial_a2a_ffn(
    x: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    axis_name: str,
) -> jax.Array:
    """Baseline: one all-to-all dispatch, expert FFN, one combine A2A.

    x: (E, C, D) tokens grouped by destination expert (E global experts,
    E = g * E_local).  Returns (E, C, D) tokens back in source layout.
    """
    g = lax.axis_size(axis_name)
    e, c, d = x.shape
    e_local = e // g
    # dispatch: split expert dim over devices, concat source dim.
    recv = lax.all_to_all(
        x.reshape(g, e_local, c, d), axis_name, split_axis=0, concat_axis=0
    )  # (g, e_local, c, d): tokens from every source for my experts
    expert_in = recv.transpose(1, 0, 2, 3).reshape(e_local, g * c, d)
    expert_out = _ffn(expert_in, w_up, w_down)
    send = expert_out.reshape(e_local, g, c, d).transpose(1, 0, 2, 3)
    back = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0)
    return back.reshape(e, c, d)


def skewed_chunk_sizes(capacity: int, profile) -> tuple[int, ...]:
    """Integer per-chunk capacity slice sizes following an expert load
    profile (:class:`repro.core.workload.StepProfile`).

    Deterministic largest-remainder quantization; zero-sized chunks
    (masked profile tail, experts that received nothing) are kept in the
    tuple so chunk indices line up with profile steps — the kernel path
    simply skips them.
    """
    sizes = profile.quantize(capacity)
    assert sum(sizes) == capacity
    return sizes


def ficco_a2a_ffn(
    x: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    axis_name: str,
    chunks: int | None = None,
    chunk_sizes=None,
    profile=None,
    variant=None,
) -> jax.Array:
    """FiCCO: capacity dimension cut into chunks; each chunk's dispatch
    A2A overlaps the previous chunk's expert GEMM (XLA async collectives
    on the ICI DMA engines do the hiding).

    The default cut is uniform (``chunks`` slices of ``C/chunks``).  The
    **skew-aware path** follows a non-uniform expert load instead: pass
    ``chunk_sizes`` (static ints summing to the capacity ``C``) or a
    ``profile`` (:class:`repro.core.workload.StepProfile`, quantized via
    :func:`skewed_chunk_sizes`).  Hot-expert token mass then travels in
    proportionally larger chunks whose expert GEMMs are also larger —
    the layout the ragged schedule engine (``simulate(...,
    profile=...)``, ``evaluate_ragged_grid``) models.  All sizes are
    trace-time constants, so the loop unrolls jit-compatibly with one
    dispatch/combine A2A pair per non-empty chunk.

    ``variant`` (a :class:`repro.tune.KernelVariant`) supplies the
    uniform chunk count when ``chunks``/``chunk_sizes``/``profile`` don't
    pin one, and its dispatch order: ``"reverse"`` issues the chunk
    A2A+FFN pairs last-to-first (front-loading a skewed profile's tail
    mass) while outputs are still reassembled in capacity order, so
    results are bit-identical across variants.
    """
    g = lax.axis_size(axis_name)
    e, c, d = x.shape
    if variant is None and chunks is None and chunk_sizes is None:
        from repro.tune.registry import resolve_variant

        variant = resolve_variant("ficco_a2a_ffn", group=g, profile=profile)
    if chunk_sizes is None and profile is not None:
        chunk_sizes = skewed_chunk_sizes(c, profile)
    if chunk_sizes is None:
        from_variant = chunks is None and variant is not None
        if from_variant:
            chunks = int(variant.chunks)
        n_chunks = chunks or g
        if c % n_chunks:
            if from_variant and c % g == 0:
                n_chunks = g  # promoted cut doesn't divide; classic cut
            else:
                return serial_a2a_ffn(x, w_up, w_down, axis_name=axis_name)
        chunk_sizes = (c // n_chunks,) * n_chunks
    else:
        chunk_sizes = tuple(int(s) for s in chunk_sizes)
        if any(s < 0 for s in chunk_sizes) or sum(chunk_sizes) != c:
            raise ValueError(
                f"chunk_sizes {chunk_sizes} must be >= 0 and sum to "
                f"capacity {c}"
            )
    e_local = e // g
    offsets = []
    offset = 0
    for c_c in chunk_sizes:
        offsets.append(offset)
        offset += c_c
    order = list(range(len(chunk_sizes)))
    if variant is not None and variant.dispatch_order == "reverse":
        order.reverse()
    outs: list = [None] * len(chunk_sizes)
    for idx in order:
        c_c = chunk_sizes[idx]
        if c_c == 0:
            continue  # empty chunk (masked tail / unloaded expert slot)
        piece = lax.dynamic_slice(x, (0, offsets[idx], 0), (e, c_c, d))
        recv = lax.all_to_all(
            piece.reshape(g, e_local, c_c, d),
            axis_name,
            split_axis=0,
            concat_axis=0,
        )
        expert_in = recv.transpose(1, 0, 2, 3).reshape(e_local, g * c_c, d)
        expert_out = _ffn(expert_in, w_up, w_down)
        send = expert_out.reshape(e_local, g, c_c, d).transpose(1, 0, 2, 3)
        back = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0)
        outs[idx] = back.reshape(e, c_c, d)
    pieces = [o for o in outs if o is not None]
    if len(pieces) == 1:
        return pieces[0]
    return jnp.concatenate(pieces, axis=1)


__all__ = ["serial_a2a_ffn", "ficco_a2a_ffn", "skewed_chunk_sizes"]
