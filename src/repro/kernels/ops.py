"""Jit'd public wrappers around the Pallas kernels.

The backend decides how a kernel runs: compiled Mosaic on a TPU, the
Mosaic interpreter on the CPU (tests and small-size rehearsals).  Any
other backend is an error rather than a silent switch to the interpreter.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.chunked_gemm import accumulate_matmul, chunked_matmul
from repro.kernels.dma_exchange import (
    a2a_chunk_exchange,
    ficco_uniform_fused_1d_dma,
)
from repro.kernels.ficco_ag_matmul import ficco_ag_matmul_fused


def _interpret() -> bool:
    """True on the CPU backend, False on a TPU; raises on anything else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need a TPU or the CPU interpreter; "
        f"the default backend is {backend!r}"
    )


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def matmul(x, w, *, block_m=128, block_n=128, block_k=128):
    return chunked_matmul(
        x, w,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def matmul_accumulate(c, x, w, *, block_m=128, block_n=128, block_k=128):
    return accumulate_matmul(
        c, x, w,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=_interpret(),
    )


def chunk_exchange(chunk, *, axis_name, group):
    """shard_map-internal: DMA all-to-all of one FiCCO chunk."""
    return a2a_chunk_exchange(
        chunk, axis_name=axis_name, group=group, interpret=_interpret()
    )


def ag_matmul_dma(x, w, *, axis_name):
    """shard_map-internal: uniform-fused-1D with Pallas DMA comm."""
    return ficco_uniform_fused_1d_dma(
        x, w, axis_name=axis_name, interpret=_interpret()
    )


def ag_matmul_fused(x, w, *, axis_name):
    """shard_map-internal: fully fused DMA+MXU pipeline (beyond-paper)."""
    return ficco_ag_matmul_fused(
        x, w, axis_name=axis_name, interpret=_interpret()
    )


__all__ = [
    "matmul",
    "matmul_accumulate",
    "chunk_exchange",
    "ag_matmul_dma",
    "ag_matmul_fused",
]
