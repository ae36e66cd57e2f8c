"""Training rows of a learnable stream: each token one or two ids above
the last, from a random start; every row different.

Mix keys: ``batch``, ``seq_len``.  ``batch(...)`` gives (batch, seq_len)
int32 ids.
"""

import numpy as np

from chipbench import traffic


def batch(mix, vocab: int, seed: int, i: int) -> np.ndarray:
    rng = traffic.rng(seed, 1, i)
    b, s = mix["batch"], mix["seq_len"]
    base = rng.integers(0, vocab, (b, 1))
    steps = rng.integers(1, 3, (b, s))
    return ((base + np.cumsum(steps, axis=1)) % vocab).astype(np.int32)
