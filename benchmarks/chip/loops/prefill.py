"""Prefill: a closed loop of ``make_prefill`` (jitted) on the
configuration's mesh.  The generator's ``batch`` gives (batch, seq_len)
token ids."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.family import Work
from chipbench.loops import Context, gap_numbers, make_params, span


def program(ctx: Context):
    """``make_prefill`` jitted, with the shapes of the weights, sharded as
    the program shards them on the configuration's mesh, and of the
    replicated tokens."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model
    from repro.parallel.sharding import fix_param_specs
    from repro.serve.engine import make_prefill

    model = build_model(ctx.family.program_config(ctx.config))
    axes = ctx.config["mesh"]
    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(tuple(axes.values()), tuple(axes),
                     devices=ctx.devices and ctx.devices[:n])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = fix_param_specs(model.param_specs(), shapes, mesh)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    toks = jax.ShapeDtypeStruct((ctx.mix["batch"], ctx.mix["seq_len"]),
                                jnp.int32, sharding=NamedSharding(mesh, P()))
    return jax.jit(make_prefill(model)), (params, {"tokens": toks}), mesh


class Loop:
    """Closed loop of ``make_prefill`` (jitted) on the configuration's
    mesh."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.dims = ctx.dims
        self.mix = ctx.mix

    def _tokens(self, i: int) -> np.ndarray:
        return self.ctx.batch(i)

    def setup(self) -> None:
        from repro.obs import metrics

        seed = self.ctx.seed
        self.fn, (want, batch), self.mesh = program(self.ctx)
        self.params = make_params(self.ctx, want)
        rep = batch["tokens"].sharding
        self.inputs = [
            {"tokens": jax.device_put(self._tokens(i), rep)}
            for i in range(self.mix["distinct_batches"])
        ]
        self.counters = {
            name: metrics.get_metrics().counter(name)
            for name in self.ctx.limits.get("counters", {})
        }
        self.argmax = jax.jit(lambda lg: jnp.argmax(lg, -1).astype(jnp.int32))
        with jax.sharding.set_mesh(self.mesh):
            out = self.fn(self.params, self.inputs[0])
            self.argmax(out).block_until_ready()
        # The call whose logits are compared, drawn from the seed among
        # the first few calls of the window.
        self.pick = int(np.random.default_rng((seed, 5)).integers(0, 4))

    def window(self, seconds: float) -> dict:
        n = len(self.inputs)
        pending, calls = None, 0
        self.kept = None
        t0 = time.perf_counter()
        with jax.sharding.set_mesh(self.mesh):
            while True:
                with span("bench/prefill_call"):
                    out = self.fn(self.params, self.inputs[calls % n])
                if calls <= self.pick:
                    self.kept = (calls % n, out)
                calls += 1
                if pending is not None:
                    with span("bench/wait"):
                        pending.block_until_ready()
                pending = out
                if time.perf_counter() - t0 >= seconds:
                    break
            with span("bench/wait"):
                pending.block_until_ready()
        elapsed = time.perf_counter() - t0
        b, s = self.mix["batch"], self.mix["seq_len"]
        work = self.ctx.family.prefill(self.dims, b, s)
        finite = bool(jnp.isfinite(self.kept[1]).all())
        return {
            "attempted": calls, "failed": 0 if finite else 1,
            "elapsed": elapsed, "steps": calls,
            "work": Work(work.flops * calls, work.bytes * calls),
            "metrics": {"prefill_tokens_per_s": calls * b * s / elapsed},
        }

    def release(self) -> None:
        with jax.sharding.set_mesh(self.mesh):
            idx, out = self.kept
            self.chosen = np.asarray(self.argmax(out))
        self.kept_batch = idx
        self.kept = self.params = self.inputs = self.fn = None

    def check(self, control: bool = False) -> dict:
        tokens = self._tokens(self.kept_batch)
        devs = jax.devices()[: self.ctx.chips]
        blocks = [jax.device_put(tokens[i:i + 1], devs[i % len(devs)])
                  for i in range(tokens.shape[0])]
        chosen = [jax.device_put(self.chosen[i:i + 1], devs[i % len(devs)])
                  for i in range(tokens.shape[0])]
        gaps, cgaps = self.ctx.family.token_gaps(
            self.dims, self.ctx.seed, blocks, chosen, control=control)
        masks = [np.ones(t.shape, bool) for t in chosen]
        out = gap_numbers(gaps, cgaps, masks, control,
                           self.ctx.limits["limits"]["token_gap"])
        for name, bound in self.ctx.limits.get("counters", {}).items():
            value = self.counters[name].value
            if "max" in bound:
                out[name] = (value, bound["max"])
            if "min" in bound:
                # How many short of the least: held at 0.
                out[f"{name} short of {bound['min']}"] = (
                    max(0, bound["min"] - value), 0)
        return out
