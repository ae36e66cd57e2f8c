"""What the traffic generators in ``generators/`` share.

Every seed gets the same amount of work: the same sizes, drawn once as
quantiles of the stated distributions, in an order and with token ids
that the seed chooses.  The program receives only the arrays the
generators make.
"""

from __future__ import annotations

import math
import statistics
from typing import Any

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """The seed's generator for one stream of draws."""
    return np.random.default_rng((seed, *stream))


def lognormal_sizes(spec: dict[str, Any], n: int) -> list[int]:
    """``n`` sizes at the mid-quantiles of a log-normal with the given
    ``median`` and ``sigma``, each at least 1."""
    dist = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    return [max(1, round(math.exp(dist.inv_cdf((k + 0.5) / n))))
            for k in range(n)]
