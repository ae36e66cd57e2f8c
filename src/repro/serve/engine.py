"""Serving substrate: prefill + batched greedy decode engine.

``make_serve_step`` is what the decode dry-run shapes lower: ONE new token
against a ``seq_len`` KV cache.  The engine adds a minimal continuous-batch
loop on top for the runnable serving example.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import Model, build_model
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.parallel.context import overlap_context


# Arguments of the serve step that its jit donates: the cache, so the
# step's in-place writes land in the buffer it was given.
SERVE_STEP_DONATE = (1,)


def make_serve_step(model: Model) -> Callable:
    """(params, cache, tokens (B,1), pos) -> (logits, new_cache)."""

    def serve_step(params, cache, tokens, pos):
        with overlap_context(model.config.overlap):
            return model.decode_step(params, cache, tokens, pos)

    return serve_step


def make_prefill(model: Model) -> Callable:
    def prefill(params, batch):
        with overlap_context(model.config.overlap):
            logits, _ = model.forward(params, batch)
        return logits

    return prefill


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class DecodeEngine:
    """Tiny batched greedy engine over the jitted serve_step.

    Prompts are fed token-by-token through the decode path (prefill via
    decode keeps the engine simple and exercises the cache exactly as the
    dry-run shapes do).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        batch_size: int = 4,
        cache_len: int = 128,
        enc_len: int = 0,
        adapt=None,
    ):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.batch = batch_size
        self.cache_len = cache_len
        self.cache = self.model.init_cache(
            batch_size, cache_len, enc_len=enc_len
        )
        self.step_fn = jax.jit(
            make_serve_step(self.model), donate_argnums=SERVE_STEP_DONATE
        )
        # Online-adaptation tier (repro.serve.adapt.AdaptiveTier): when
        # set, every run() streams its request-load digest through the
        # tier and records the tuned overlap schedule for the batch.
        self.adapt = adapt
        self.last_decision = None

    def run(self, requests: list[Request]) -> list[Request]:
        assert len(requests) <= self.batch
        # A batch whose requests want zero new tokens (all
        # max_new_tokens=0, or an empty/dummy-pad-only batch) has
        # nothing to emit — skip the decode loop entirely instead of
        # burning max_prompt + max_new jitted steps producing nothing.
        if not any(len(r.out) < r.max_new_tokens for r in requests):
            for r in requests:
                r.done = True
            return requests
        # left-align all prompts; pad batch with a dummy request
        reqs = list(requests) + [
            Request(np.zeros(1, np.int32), 0)
            for _ in range(self.batch - len(requests))
        ]
        max_prompt = max(len(r.prompt) for r in reqs)
        max_new = max((r.max_new_tokens for r in reqs), default=0)
        reg = _metrics.get_metrics()
        steps_c = reg.counter("serve/steps")
        tokens_c = reg.counter("serve/tokens")
        overlap_args = {}
        if self.adapt is not None:
            self.last_decision = self.adapt.pick_for_requests(
                requests, self.cfg
            )
            # Surface the batch's overlap decision on the run span so a
            # merged fleet trace reads which schedule served which
            # batch without joining against the audit log.  The hook is
            # duck-typed (tests stub it), so only annotate when the
            # decision actually carries a schedule.
            sched = getattr(self.last_decision, "schedule", None)
            if sched is not None:
                overlap_args = {
                    "overlap_schedule": sched.value,
                    "overlap_tier": self.last_decision.source,
                }
        with _trace.span(
            "serve/run", "serve",
            n_requests=len(requests), batch=self.batch,
            max_prompt=max_prompt, max_new=max_new, **overlap_args,
        ):
            for pos in range(max_prompt + max_new):
                # A step's four phases, each a span: where the device
                # waits on the host shows up under one of them.
                with _trace.span("serve/step", "serve", pos=pos) as sp:
                    with _trace.span("serve/feed", "serve"):
                        feed = []
                        for r in reqs:
                            if pos < len(r.prompt):
                                feed.append(r.prompt[pos])
                            elif r.out:
                                feed.append(r.out[-1])
                            else:
                                feed.append(0)
                        tok = jnp.asarray(np.asarray(feed, np.int32)[:, None])
                    with _trace.span("serve/dispatch", "serve"):
                        logits, self.cache = self.step_fn(
                            self.params, self.cache, tok, jnp.int32(pos)
                        )
                    with _trace.span("serve/fetch", "serve"):
                        nxt = np.asarray(jnp.argmax(logits[:, 0], -1))
                    with _trace.span("serve/emit", "serve"):
                        emitted = 0
                        for i, r in enumerate(reqs[: len(requests)]):
                            if (
                                pos >= len(r.prompt) - 1
                                and len(r.out) < r.max_new_tokens
                            ):
                                r.out.append(int(nxt[i]))
                                emitted += 1
                    sp.set(tokens=emitted)
                steps_c.inc()
                tokens_c.inc(emitted)
                if all(
                    len(r.out) >= r.max_new_tokens
                    for r in reqs[: len(requests)]
                ):
                    break
        for r in requests:
            r.done = True
        return requests
