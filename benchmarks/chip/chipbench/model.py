"""A configuration file's sizes, as the benchmark reads them.

The benchmark's reference, weights and counters read :class:`Dims`, which
is built from the configuration file alone.  :func:`program_config`
builds the program's own config object from the same file, and refuses a
file whose sizes the program's registry would not run as written.
"""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass(frozen=True)
class Dims:
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    norm: str  # rmsnorm | nonparametric_ln
    norm_eps: float
    tie_embeddings: bool
    rope_theta: float
    dtype: str

    @classmethod
    def from_config(cls, cfg: dict[str, Any]) -> "Dims":
        m = cfg["model"]
        return cls(**{f.name: m[f.name] for f in dataclasses.fields(cls)})


# Keys of the file's "model" group that the program's config object holds
# under the same name.  The rest (norm_eps) are read by the reference
# only; the program fixes them in code.
_PROGRAM_KEYS = (
    "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
    "d_ff", "vocab_size", "norm", "tie_embeddings", "rope_theta", "dtype",
)


def program_config(cfg: dict[str, Any]):
    """The program's ``ModelConfig`` for this file: the registry entry
    named by ``arch`` with the file's sizes and overlap settings."""
    from repro.configs import get_config
    from repro.configs.base import OverlapConfig

    base = get_config(cfg["arch"])
    m = cfg["model"]
    changes = {k: m[k] for k in _PROGRAM_KEYS}
    changes["overlap"] = OverlapConfig(**cfg["overlap"])
    changes.update(cfg.get("options", {}))
    out = dataclasses.replace(base, **changes)
    if out.resolved_head_dim != m["head_dim"]:
        raise ValueError(f"{cfg['arch']}: head_dim {out.resolved_head_dim}")
    if out.family.value != "dense" or out.moe or out.mla:
        raise ValueError(f"{cfg['arch']}: not of the dense family")
    return out
