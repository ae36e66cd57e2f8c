"""The work a step requires, counted from shapes: FLOPs and HBM bytes.

These are the counts that every ``mfu.*`` metric divides by, so they
count what the algorithm needs and nothing the program chooses to add:
no remat recompute, no masked attention blocks, no padding rows, no
unused cache slots.  Each count is for the dense family (GQA attention,
gated MLP) described by :class:`chipbench.model.Dims`.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

from chipbench.model import Dims
from chipbench.peaks import Peaks

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def roofline_s(self, peaks: Peaks, chips: int = 1) -> tuple[float, str]:
        """Least time on ``chips`` chips, and which peak bounds it."""
        t_flops = self.flops / (chips * peaks.bf16_flops)
        t_bytes = self.bytes / (chips * peaks.hbm_bytes_per_s)
        if t_flops >= t_bytes:
            return t_flops, "compute"
        return t_bytes, "memory"


def layer_matmul_params(m: Dims) -> int:
    q = m.d_model * m.num_heads * m.head_dim
    kv = 2 * m.d_model * m.num_kv_heads * m.head_dim
    o = m.num_heads * m.head_dim * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return q + kv + o + mlp


def matmul_params(m: Dims) -> int:
    """Parameters that take part in a matrix product for every token:
    all layers and the output projection (the embedding lookup is not a
    product)."""
    return m.num_layers * layer_matmul_params(m) + m.vocab_size * m.d_model


def param_count(m: Dims) -> int:
    n = matmul_params(m)
    if not m.tie_embeddings:
        n += m.vocab_size * m.d_model  # the input embedding table
    return n


def _attn_pair_flops(m: Dims, pairs: float) -> float:
    """Scores and weighted values for ``pairs`` (query, key) pairs."""
    return m.num_layers * 2 * 2 * m.num_heads * m.head_dim * pairs


def forward(m: Dims, batch: int, seq: int) -> Work:
    """One causal forward over ``batch`` sequences of ``seq`` tokens."""
    tokens = batch * seq
    flops = 2.0 * tokens * matmul_params(m)
    flops += _attn_pair_flops(m, batch * seq * (seq + 1) / 2)
    return Work(flops, 0.0)


def train_step(m: Dims, batch: int, seq: int) -> Work:
    """Forward and backward (twice the forward), no recompute, and AdamW
    over bf16 weights and gradients with float32 moments.  Bytes: the
    weights read by the forward and the backward, the gradients written,
    and the optimizer's reads (weight, gradient, two moments) and writes
    (weight, two moments)."""
    fwd = forward(m, batch, seq)
    p = param_count(m)
    byts = p * (2 * BF16 + BF16) + p * (BF16 + BF16 + 2 * F32)
    byts += p * (BF16 + 2 * F32)
    return Work(3 * fwd.flops, float(byts))


def prefill(m: Dims, batch: int, seq: int) -> Work:
    """Forward; bytes are the weights read once and the logits written."""
    fwd = forward(m, batch, seq)
    byts = param_count(m) * BF16 + batch * seq * m.vocab_size * BF16
    return Work(fwd.flops, float(byts))


def kv_bytes_per_token(m: Dims) -> int:
    return m.num_layers * 2 * m.num_kv_heads * m.head_dim * BF16


def decode_step(m: Dims, positions: Iterable[int]) -> Work:
    """One decode step of the requests still active, each at its
    position (0-based) in its own sequence.  Bytes: every weight once,
    the cache entries up to each request's position, and the new entry
    written.  Finished or padding rows and unused slots do not count."""
    pos = list(positions)
    if not pos:
        return Work()
    n = len(pos)
    ctx = sum(p + 1 for p in pos)
    flops = 2.0 * n * matmul_params(m) + _attn_pair_flops(m, ctx)
    byts = matmul_params(m) * BF16 + kv_bytes_per_token(m) * (ctx + n)
    return Work(flops, float(byts))


def decode_batch(m: Dims, prompt_lens, new_tokens, steps: int) -> Work:
    """All ``steps`` decode steps of one static batch: request ``i`` is
    active from position 0 until it has emitted its last token, at
    position ``prompt_lens[i] + new_tokens[i] - 2``."""
    total = Work()
    for s in range(steps):
        total = total + decode_step(m, [
            s for p, n in zip(prompt_lens, new_tokens) if s <= p + n - 2
        ])
    return total
