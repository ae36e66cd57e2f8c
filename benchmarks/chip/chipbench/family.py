"""What the model families share, and what a family provides.

A configuration file names its ``family``, a file
``families/<family>.py`` that the harness finds by name, as it finds a
mix's loop and generator.  The loops reach the family only through their
context (``chipbench.loops.Context.family``).  A family provides:

* ``Dims``, its sizes as the benchmark reads them, and ``dims(config)``,
  built from the configuration file alone (with ``vocab_size``, which
  the generators read);
* ``program_config(config)``: the program's own config object for the
  file, refusing sizes that the family does not describe;
* ``program_params(dims, key)``: the weights of ``seed_key(seed)`` in the
  program's parameter tree, made in one jitted call; and, for training,
  ``leaf_groups(dims)`` and ``program_leaf(params, name)``, the leaves
  the comparison reads;
* the work a step requires (``Work``): ``prefill(dims, batch, seq)``,
  ``decode_batch(dims, prompt_lens, new_tokens, steps)`` and
  ``train_step(dims, batch, seq)``;
* the plain float32 reference: ``token_gaps(dims, seed, blocks, chosen,
  control=...)``, with its float8 control, and ``train_reference(dims,
  seed, batches, opt, quant=None, block=1)``.

What they share is here: the seed's key, ``Work``, and the reference's
products at the highest precision with the float8 control's rounding.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.peaks import Peaks

HIGHEST = lax.Precision.HIGHEST
_F8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A key for any whole ``seed``: the high word is folded in, so seeds
    that differ above 32 bits give different weights."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass(frozen=True)
class Work:
    """FLOPs and HBM bytes that a step requires, counted from shapes."""

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


def fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor scale; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def mm(a, b, quant):
    """A reference product at ``Precision.HIGHEST``; with ``quant="fp8"``
    (the control), its operands rounded to float8 first."""
    if quant == "fp8":
        a, b = fp8(a), fp8(b)
    elif quant is not None:
        raise ValueError(f"quant {quant!r}")
    return jnp.matmul(a, b, precision=HIGHEST)
