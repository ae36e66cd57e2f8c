"""Chat requests whose prompt and answer lengths are log-normal.

Mix keys: ``batch`` (requests in a batch), ``groups`` (how many distinct
batches there are; batch ``i`` is group ``i % groups``), ``cache_len``
(the context a request's prompt and answer share), and ``prompt_len``
and ``new_tokens``, each ``{"median", "sigma"}``.

The ``batch * groups`` lengths of each kind sit at the mid-quantiles of
their distribution, so every seed gets the same sizes.  Prompt and
answer lengths are paired by a fixed permutation, so they are
independent, and the pairs are dealt into groups by another.  A prompt
too long for the context with its answer is cut to fit, as a server
keeps its answer's room.  The seed chooses the order of each batch and
every token id.
"""

import numpy as np

from chipbench import traffic


def sizes(mix) -> list:
    """For each group, its (prompt, new tokens) pairs."""
    b, g, ctx = mix["batch"], mix.get("groups", 1), mix["cache_len"]
    n = b * g
    prompts = traffic.lognormal_sizes(mix["prompt_len"], n)
    news = traffic.lognormal_sizes(mix["new_tokens"], n)
    fixed = np.random.default_rng(0)
    pair, deal = fixed.permutation(n), fixed.permutation(n)
    pairs = []
    for k in deal:
        p, q = prompts[k], news[pair[k]]
        if q >= ctx:
            raise ValueError(f"an answer of {q} tokens outgrows the context")
        pairs.append((min(p, ctx - q), q))
    return [pairs[j * b:(j + 1) * b] for j in range(g)]


def batch(mix, vocab: int, seed: int, i: int) -> list:
    """Batch ``i``: a list of (prompt ids, new tokens), in an order the
    seed chooses."""
    rng = traffic.rng(seed, 3, i)
    pairs = sizes(mix)[i % mix.get("groups", 1)]
    return [(rng.integers(0, vocab, pairs[k][0], dtype=np.int32),
             pairs[k][1]) for k in rng.permutation(len(pairs))]
