"""Three-term roofline analysis from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.  Collective
bytes are NOT in cost_analysis: we parse the compiled HLO text and sum the
operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.  Hardware constants per the brief:
197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI per chip.
"""

from __future__ import annotations

import dataclasses
import json
import re

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|[\w\[\]{},]+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(",
    re.MULTILINE,
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, float]
    count_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum output-shape bytes of every collective op in the HLO.

    ``-start``/``-done`` async pairs are counted once (on the start op);
    synchronous forms count directly.
    """
    bytes_by: dict[str, float] = {}
    count_by: dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, kind, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue
        b = _shape_bytes(shape_str)
        bytes_by[kind] = bytes_by.get(kind, 0.0) + b
        count_by[kind] = count_by.get(kind, 0) + 1
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collectives: dict[str, float]
    collective_counts: dict[str, int]
    model_flops: float  # 6*N*D (or 6*N_active*D for MoE)
    bytes_per_device: float  # peak memory from memory_analysis

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * LINK_BW)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs: how much compiled compute is useful
        (catches remat / redundancy waste).  > 1 means the HLO counter
        under-reports (e.g. decode where 6ND is not the right model)."""
        if self.hlo_flops <= 0:
            return float("nan")
        return self.model_flops / self.hlo_flops

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "collectives": self.collectives,
            "collective_counts": self.collective_counts,
            "model_flops": self.model_flops,
            "bytes_per_device": self.bytes_per_device,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def analyze(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    compiled,
    model_flops: float,
) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older API returned [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    nbytes = float(
        cost.get("bytes accessed", cost.get("bytes_accessed", 0.0))
    )
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem_bytes = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            - getattr(ma, "alias_size_in_bytes", 0)
        )
    except Exception:
        mem_bytes = float("nan")
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=flops,
        hlo_bytes=nbytes,
        collective_bytes=coll.total_bytes,
        collectives=coll.bytes_by_kind,
        collective_counts=coll.count_by_kind,
        model_flops=model_flops,
        bytes_per_device=mem_bytes,
    )


def count_params(cfg) -> float:
    """Total and active parameter counts from the config (analytic)."""
    d, ff, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_layers
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    from repro.models.model import build_model
    import jax
    import numpy as np

    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    total = sum(float(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    return total


def active_params(cfg) -> float:
    """Active (per-token) params: MoE counts only top-k + shared experts."""
    total = count_params(cfg)
    if not cfg.moe:
        return total
    # subtract the inactive routed experts' share
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    held = cfg.moe.held[1]
    d, f = cfg.d_model, cfg.moe.d_ff_expert
    n_moe_layers = ((cfg.num_layers - cfg.moe.first_dense_layers)
                    // cfg.moe.every_k_layers)
    routed = 3.0 * d * f * held * n_moe_layers
    # a layer holding a share computes its share of the k, in expectation
    active_routed = 3.0 * d * f * k * held / e * n_moe_layers
    return total - routed + active_routed


def model_flops_for(cfg, shape_cfg, kind: str) -> float:
    """MODEL_FLOPS = 6*N_active*D for training; 2*N_active*D for inference
    forward; decode D = global_batch tokens (one step)."""
    n = active_params(cfg)
    if kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape_cfg.global_batch
