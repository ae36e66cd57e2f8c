"""Training: a closed loop of ``make_train_step`` (jitted, state donated).

The first three steps run in set-up through the window's own call and
feed; the reference follows them (loss, first gradient, change)."""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.family import Work, seed_key
from chipbench.loops import Context, make_params, span, train_numbers


def program(ctx: Context):
    """``make_train_step`` jitted with its state donated, with the shapes
    of the state (weights and optimizer moments) and of a batch."""
    from repro.models.model import build_model
    from repro.train import optimizer as opt
    from repro.train.loop import make_train_step

    model = build_model(ctx.family.program_config(ctx.config))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda p: {"params": p, "opt_state": opt.init_state(p)}, params)
    toks = jax.ShapeDtypeStruct((ctx.mix["batch"], ctx.mix["seq_len"]),
                                jnp.int32)
    step = jax.jit(make_train_step(
        model, opt.OptimizerConfig(**ctx.config["optimizer"])),
        donate_argnums=0)
    return step, (state, {"tokens": toks, "labels": toks}), None


class Loop:
    """Closed loop of ``make_train_step`` (jitted, state donated)."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.dims = ctx.dims
        self.opt = dict(ctx.config["optimizer"])

    def _batch(self, i: int) -> np.ndarray:
        return self.ctx.batch(i)

    def setup(self) -> None:
        from repro.train import optimizer as opt

        m, seed, fam = self.dims, self.ctx.seed, self.ctx.family
        self.step, (want, _), _ = program(self.ctx)
        params = make_params(self.ctx, want["params"])
        state = {"params": params, "opt_state": opt.init_state(params)}
        n = self.ctx.mix["distinct_batches"]
        self.batches = [
            {"tokens": t, "labels": t}
            for t in (jnp.asarray(self._batch(i)) for i in range(n))
        ]
        b1 = self.opt["b1"]
        first_grad = jax.jit(lambda st: {
            k: jnp.linalg.norm(
                fam.program_leaf(st["opt_state"]["m"], k).ravel()
            ) / (1 - b1)
            for k in fam.leaf_groups(m)})
        change = jax.jit(lambda st, key: {
            k: jnp.linalg.norm((
                fam.program_leaf(st["params"], k).astype(jnp.float32)
                - fam.program_leaf(fam.program_params(m, key), k)
                .astype(jnp.float32)).ravel())
            for k in fam.leaf_groups(m)})
        # The first three steps go through the window's own call and feed;
        # they compile the step and are what the reference follows.
        self.losses = []
        for i in range(3):
            state, met = self.step(state, self.batches[i])
            self.losses.append(float(met["loss"]))
            if i == 0:
                self.grad_norms = jax.tree.map(float, first_grad(state))
        self.change_norms = jax.tree.map(
            float, change(state, seed_key(seed)))
        self.state = state
        self.next = 3

    def window(self, seconds: float) -> dict:
        b, s = self.ctx.mix["batch"], self.ctx.mix["seq_len"]
        n = len(self.batches)
        state, pending, steps = self.state, None, 0
        t0 = time.perf_counter()
        while True:
            with span("bench/train_step"):
                state, met = self.step(state, self.batches[self.next % n])
            self.next += 1
            steps += 1
            if pending is not None:
                with span("bench/wait"):
                    pending.block_until_ready()
            pending = met["loss"]
            if time.perf_counter() - t0 >= seconds:
                break
        with span("bench/wait"):
            final = float(pending)
        elapsed = time.perf_counter() - t0
        self.state = state
        work = self.ctx.family.train_step(self.dims, b, s)
        return {
            "attempted": steps,
            "failed": 0 if np.isfinite(final) else steps,
            "elapsed": elapsed,
            "steps": steps,
            "work": Work(work.flops * steps, work.bytes * steps),
            "metrics": {"train_tokens_per_s": steps * b * s / elapsed},
        }

    def release(self) -> None:
        self.state = self.step = self.batches = None

    def check(self, control: bool = False) -> dict:
        ref = self.ctx.family.train_reference(
            self.dims, self.ctx.seed, [self._batch(i) for i in range(3)],
            self.opt, block=self.ctx.mix.get("reference_block", 1))
        for k in sorted(ref["grad_norms"]):
            print(f"leaf {k}: first gradient {self.grad_norms[k]!r} "
                  f"(reference {ref['grad_norms'][k]!r}), change "
                  f"{self.change_norms[k]!r} (reference "
                  f"{ref['change_norms'][k]!r})", file=sys.stderr)
        print(f"losses {self.losses!r} (reference {ref['losses']!r})",
              file=sys.stderr)
        return train_numbers(self, ref, self.ctx.limits["limits"])
