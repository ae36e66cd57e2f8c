"""Faults planted under the harness, each where the program would make it.

``plant(name, ...)`` returns the (module, attribute, replacement) that a
test patches in.  A run with any of them must come out not correct.

* ``state_unchanged``: the train step returns the state it was given.
* ``half_batch``: the train step sees only the first half of each batch,
  the mean taken over the rest.
* ``token_altered``: the decode step puts another token first, at one
  position, in every row.
* ``answer_altered``: the prefill puts another token first at one
  position of every sequence.
* ``exchange_left_out``: the DMA exchange between chips copies only the
  local chunk.
"""

import jax
import jax.numpy as jnp


def plant(name: str, **kw):
    if name in ("state_unchanged", "half_batch"):
        import repro.train.loop as mod

        real = mod.make_train_step

        def make(model, ocfg, **opts):
            step = real(model, ocfg, **opts)

            def broken(state, batch):
                if name == "half_batch":
                    half = batch["tokens"].shape[0] // 2
                    return step(state, jax.tree.map(lambda a: a[:half],
                                                    batch))
                _, met = step(state, batch)
                return state, met

            return broken

        return mod, "make_train_step", make
    if name == "token_altered":
        import repro.serve.engine as mod

        real, at = mod.make_serve_step, kw["position"]

        def make(model):
            step = real(model)

            def broken(params, cache, tokens, pos):
                logits, cache = step(params, cache, tokens, pos)
                v = logits.shape[-1]
                other = (jnp.argmax(logits, -1) + 1) % v
                bump = jax.nn.one_hot(other, v, dtype=logits.dtype) * 1e4
                return jnp.where(pos == at, logits + bump, logits), cache

            return broken

        return mod, "make_serve_step", make
    if name == "answer_altered":
        import repro.serve.engine as mod

        real = mod.make_prefill

        def make(model):
            fn = real(model)

            def broken(params, batch):
                logits = fn(params, batch)
                row = logits[:, 5]
                return logits.at[:, 5].set(jnp.roll(row, 1, axis=-1))

            return broken

        return mod, "make_prefill", make
    if name == "exchange_left_out":
        import repro.kernels.dma_exchange as mod

        def local_only(chunk, *, axis_name, group, interpret=False,
                       reverse=False):
            return jnp.broadcast_to(chunk, (group, *chunk.shape))

        return mod, "a2a_chunk_exchange", local_only
    raise ValueError(name)
