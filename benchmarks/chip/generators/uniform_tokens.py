"""Prompts of uniform random token ids, all of one length.

Mix keys: ``batch``, ``seq_len``.  ``batch(...)`` gives (batch, seq_len)
int32 ids.
"""

import numpy as np

from chipbench import traffic


def batch(mix, vocab: int, seed: int, i: int) -> np.ndarray:
    return traffic.rng(seed, 2, i).integers(
        0, vocab, (mix["batch"], mix["seq_len"]), dtype=np.int32)
