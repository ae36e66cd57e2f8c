"""Arithmetic shared by the per-layer metric readers in ``metrics/``."""

from __future__ import annotations

import re
from typing import Optional

from chipbench import trace

# The Pallas DMA exchange (``a2a_chunk_exchange``), as a TPU trace prints
# its event: ``%shard_map.72 = bf16[4,1024,4096]{...} custom-call(
# bf16[1024,4096]{...} %squeeze.268), custom_call_target="tpu_custom_call"``.
# The trace does not carry the Pallas kernel's name, so the exchange is
# known by its signature: one operand, and a result of the same type
# with one leading axis more (a gather of ``g`` chunks).  A kernel that
# computes, such as the fused AG->GEMM (a chunk and a weight panel) or
# attention (q, k and v), has more operands and is not counted.
EXCHANGE = re.compile(
    r"= (?P<t>\w+)\[\d+,(?P<d>[\d,]+)\](?:\{[^}]*\})? custom-call\("
    r"(?P=t)\[(?P=d)\](?:\{[^}]*\})? %[^\s,()]+\), "
    r'custom_call_target="tpu_custom_call"')


def step_mfu(run) -> float:
    """The whole window's share, in %, of the chips' roofline: the least
    time the work the steps required could take at the published peaks
    (the family's counters), over the time the window took."""
    least, _ = run.records["work"].roofline_s(run.peaks, run.chips)
    return 100.0 * least / run.records["elapsed"]


def roofline_bound(run) -> str:
    return run.records["work"].roofline_s(run.peaks, run.chips)[1]


def idle_share(run) -> float:
    """% of the traced window in which no operation ran on a device,
    averaged over the devices."""
    return 100.0 * trace.idle_share(run.trace)


def busy_share(run, match: re.Pattern) -> Optional[float]:
    """% of the traced window in which a device ran an op that ``match``
    finds, averaged over the devices; None if none ran."""
    secs = trace.busy_s(run.trace, match)
    return 100.0 * secs / run.trace.window_s if secs > 0 else None


def ms_per_step(run, match: re.Pattern) -> Optional[float]:
    """Device milliseconds of the ops ``match`` finds, per step of the
    window, averaged over the devices; None if none ran."""
    secs, count = trace.op_seconds(run.trace, match)
    return 1e3 * secs / run.records["steps"] if count else None
