"""Serving: a closed loop of static batches through ``DecodeEngine.run``.

The generator's ``batch`` gives a batch's requests as (prompt ids, new
tokens).  Each token is stamped on the host clock as the engine hands it
to its request, through the request's own ``out`` list."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.family import Work
from chipbench.loops import (Context, StampedTokens, gap_numbers,
                             make_params, percentile, span)


def _engine(ctx: Context):
    """The program's engine for the mix's batch and cache, without
    weights."""
    from repro.serve.engine import DecodeEngine

    return DecodeEngine(ctx.family.program_config(ctx.config), None,
                        batch_size=ctx.mix["batch"],
                        cache_len=ctx.mix["cache_len"])


def program(ctx: Context, engine=None):
    """The engine's own jitted step (its cache donated, as the engine
    jits it), with the shapes of its weights, cache, tokens and position;
    ``engine`` is the one to take it from, else a new one."""
    eng = engine or _engine(ctx)
    params = jax.eval_shape(eng.model.init, jax.random.PRNGKey(0))
    cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         eng.cache)
    args = (params, cache,
            jax.ShapeDtypeStruct((eng.batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    return eng.step_fn, args, None


class Loop:
    """Closed loop of static batches through ``DecodeEngine.run``."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.dims = ctx.dims
        self.mix = ctx.mix

    def setup(self) -> None:
        from repro.serve.engine import Request

        self.Request = Request
        self.engine = _engine(self.ctx)
        _, args, _ = program(self.ctx, self.engine)
        self.engine.params = make_params(self.ctx, args[0])
        if self.ctx.traced:
            inner = self.engine.step_fn

            def step_fn(*args):
                with span("bench/engine.step_fn"):
                    return inner(*args)

            self.engine.step_fn = step_fn
        # Two steps of a full batch run every program the window runs:
        # the step, and the engine's slicing, argmax and feed.
        warm = [Request(np.zeros(1, np.int32), max_new_tokens=2)
                for _ in range(self.mix["batch"])]
        self.engine.run(warm)
        self.done: list = []

    def window(self, seconds: float) -> dict:
        m = self.dims
        work, steps, batches = Work(), 0, 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            reqs = [
                self.Request(p, max_new_tokens=n, out=StampedTokens())
                for p, n in self.ctx.batch(batches)
            ]
            with span("bench/engine.run"):
                self.engine.run(reqs)
            n_steps = max(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
            work = work + self.ctx.family.decode_batch(
                m, [len(r.prompt) for r in reqs],
                [r.max_new_tokens for r in reqs], n_steps)
            steps += n_steps
            batches += 1
            self.done.extend(reqs)
        elapsed = time.perf_counter() - t0
        failed = sum(
            len(r.out) != r.max_new_tokens
            or not all(0 <= t < m.vocab_size for t in r.out)
            for r in self.done)
        gaps = [b - a for r in self.done
                for a, b in zip(r.out.times, r.out.times[1:])]
        tokens = sum(len(r.out) for r in self.done)
        return {
            "attempted": len(self.done), "failed": failed,
            "elapsed": elapsed, "steps": steps, "batches": batches,
            "work": work,
            "metrics": {
                "decode_tokens_per_s": tokens / elapsed,
                "itl_p95_ms": percentile(gaps, 95) * 1e3,
            },
        }

    def release(self) -> None:
        self.engine = None

    def sample(self) -> list:
        """Requests to compare, drawn from the seed: the one with the
        most tokens (prompt and answer) and others at random."""
        k = self.ctx.limits["check_requests"]
        done = self.done
        longest = max(range(len(done)), key=lambda i: (
            len(done[i].prompt) + len(done[i].out), -i))
        rest = [i for i in range(len(done)) if i != longest]
        rng = np.random.default_rng((self.ctx.seed, 4))
        pick = rng.choice(len(rest), size=min(k - 1, len(rest)),
                          replace=False)
        return [done[longest]] + [done[rest[i]] for i in sorted(pick)]

    def check(self, control: bool = False) -> dict:
        """The widest gap of a served token below the reference's best;
        with ``control``, also that of the float8 control's first token
        at the same positions (``control:token_gap``)."""
        served = self.sample()
        width = self.mix["cache_len"]
        blocks, chosen, masks = [], [], []
        for r in served:
            p, out = len(r.prompt), list(r.out)
            seq = np.zeros(width, np.int32)
            seq[:p] = r.prompt
            seq[p:p + len(out) - 1] = out[:-1]
            ch = np.zeros(width, np.int32)
            ch[p - 1:p - 1 + len(out)] = out
            mask = np.zeros(width, bool)
            mask[p - 1:p - 1 + len(out)] = True
            blocks.append(jnp.asarray(seq[None]))
            chosen.append(jnp.asarray(ch[None]))
            masks.append(mask[None])
        gaps, cgaps = self.ctx.family.token_gaps(
            self.dims, self.ctx.seed, blocks, chosen, control=control)
        return gap_numbers(gaps, cgaps, masks, control,
                            self.ctx.limits["limits"]["token_gap"])
