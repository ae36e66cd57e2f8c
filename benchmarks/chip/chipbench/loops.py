"""What the loops that drive the program's timed path share.

A mix (``traffic/<mix>.json``) names its ``loop``, a file
``loops/<loop>.py`` whose class ``Loop(ctx)`` drives the program, and its
``generator``, a file ``generators/<generator>.py`` whose ``batch(mix,
vocab, seed, i)`` makes the inputs of batch ``i`` from the seed.  The
harness finds both by name, as it finds the metric readers.  Each loop:

* ``program(ctx)``, a function of the loop's module, builds the program
  the loop times and returns ``(jitted fn, its arguments' shapes with
  their shardings, the mesh to call it under or None)``; the scope
  readers compile the same (``chipbench.scopes``);
* ``setup()`` builds that program with ``program``, makes the weights on
  the device from the seed, and runs every shape the window will use;
* ``window(seconds)`` drives that same step in a closed loop until
  ``seconds`` have passed and the work in flight has finished, and
  returns its records and end-to-end metrics;
* ``release()`` frees the program's state;
* ``check(control=False)`` then compares what the timed path produced
  with the plain reference and returns ``{name: (value, limit)}``; with
  ``control``, also ``control:<name>``, the same number with the float8
  control in the program's place.

Spans named ``bench/...`` mark, in a traced run, what the host was doing.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import numpy as np

from chipbench.family import seed_key

span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Context:
    cell: str
    config: dict[str, Any]  # the configuration file
    mix: dict[str, Any]  # the traffic file
    limits: dict[str, Any]  # the cell file
    seed: int
    chips: int
    gen: Any  # the mix's generator module
    family: Any  # the configuration's family module (chipbench.family)
    traced: bool = False
    # the devices a mesh is built from; None: jax.devices()
    devices: Optional[list] = None

    @property
    def dims(self):
        return self.family.dims(self.config)

    def batch(self, i: int):
        """The inputs of batch ``i``, from the mix's generator."""
        return self.gen.batch(self.mix, self.dims.vocab_size, self.seed, i)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def make_params(ctx: Context, want):
    """The seed's weights, made on the device in one jitted call with the
    shardings of ``want``, the program's parameter shapes (from
    ``program``), after checking that they have its tree, shapes and
    dtypes."""
    key = seed_key(ctx.seed)
    make = functools.partial(ctx.family.program_params, ctx.dims)
    got = jax.eval_shape(make, key)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("weights do not have the program's tree")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"leaf {a.shape} {a.dtype} != {b.shape} "
                             f"{b.dtype}")
    placed = all(a.sharding is not None for a in jax.tree.leaves(want))
    shardings = jax.tree.map(lambda a: a.sharding, want) if placed else None
    return jax.jit(make, out_shardings=shardings)(key)


def _rel_gaps(prog: dict, ref: dict) -> dict:
    """For each leaf: |program norm - reference norm| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    med = float(np.median(list(ref.values())))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref}


def train_numbers(prog, ref: dict, limits: dict) -> dict:
    """The training cell's compared numbers, each with its limit.

    Leaves whose first reference gradient is under a thousandth of the
    median leaf's move under Adam by round-off alone; they are left out
    of the comparison of changes."""
    med = float(np.median(list(ref["grad_norms"].values())))
    moved = {k for k, v in ref["grad_norms"].items() if v >= 1e-3 * med}
    loss_gap = max(abs(a - b) for a, b in zip(prog.losses, ref["losses"]))
    grad = _rel_gaps(prog.grad_norms, ref["grad_norms"])
    change = _rel_gaps({k: prog.change_norms[k] for k in moved},
                       {k: ref["change_norms"][k] for k in moved})
    return {
        "loss_gap": (loss_gap, limits["loss_gap"]),
        "grad_norm_gap": (max(grad.values()), limits["grad_norm_gap"]),
        "change_norm_gap": (max(change.values()),
                            limits["change_norm_gap"]),
    }


def gap_numbers(gaps, cgaps, masks, control: bool, limit: float) -> dict:
    """The widest token gap over the masked positions, with its limit;
    with ``control``, also the control's (``control:token_gap``)."""
    def widest(arrays):
        return max(float(np.max(np.asarray(g)[mk]))
                   for g, mk in zip(arrays, masks))

    out = {"token_gap": (widest(gaps), limit)}
    if control:
        out["control:token_gap"] = (widest(cgaps), limit)
    return out


class StampedTokens(list):
    """A request's output list that notes the host clock at each token."""

    def __init__(self):
        super().__init__()
        self.times: list[float] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)
