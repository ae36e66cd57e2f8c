"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU is a plane named ``/device:TPU:<n>`` whose ``XLA Ops``
line holds one event per operation run, and the host's plane holds the
benchmark's own ``jax.profiler.TraceAnnotation`` spans (named ``bench/...``)
on the same clock.  The span ``bench/window`` marks the traced window.

A TensorCore runs one operation at a time, so the union of a device's op
intervals is its busy time, and what is not covered is idle.  Events
nest: a ``while`` loop's event spans the ops of its body, so time by op
is each event's self time (its span less its children's).  An event's
name is its HLO instruction as the profiler prints it (``%fusion.3 =
bf16[...] fusion(...), kind=..., calls=...``); the metric readers match
on that text.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"

Interval = tuple  # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    devices: dict[int, list]  # device id -> sorted op intervals, in the window
    spans: list  # host spans (start, end, name), sorted by start
    window: tuple  # (start_ns, end_ns)
    unions: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def from_events(devices: dict[int, Iterable[Interval]],
                spans: Iterable[Interval]) -> Trace:
    """A trace from plain intervals (used by ``load`` and by tests)."""
    spans = sorted(spans)
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, got "
                         f"{len(windows)}")
    win = windows[0][:2]
    devs = {d: sorted(_clip(ops, *win)) for d, ops in devices.items()}
    if not devs:
        raise ValueError("the trace holds no device plane")
    return Trace(devs, spans, win)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files in {logdir}")
    return paths[0]


def load(path: str) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: dict[int, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OP_LINE:
                devices[int(m.group(1))] = [
                    (e.start_ns, e.end_ns, e.name) for e in line.events
                ]
            elif not m and plane.name.startswith("/host"):
                spans.extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    return from_events(devices, spans)


def _clip(ops, lo, hi):
    for s, e, n in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e, n


def _union(ops) -> list:
    """Merged (start, end) intervals of sorted ops."""
    out: list = []
    for s, e, _ in ops:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _select(ops, match: Optional[re.Pattern]) -> list:
    """The ops whose names ``match`` finds (all, without ``match``).  A
    name recurs in every layer and call, so each is searched once."""
    if match is None:
        return ops
    found: dict = {}
    out = []
    for op in ops:
        hit = found.get(op[2])
        if hit is None:
            hit = found[op[2]] = match.search(op[2]) is not None
        if hit:
            out.append(op)
    return out


def _busy(t: Trace, dev: int) -> list:
    """Device ``dev``'s busy intervals: the union of its ops."""
    if dev not in t.unions:
        t.unions[dev] = _union(t.devices[dev])
    return t.unions[dev]


def busy_s(t: Trace, match: Optional[re.Pattern] = None) -> float:
    """Seconds in the window in which a device ran an operation (whose
    name ``match`` finds, if given), averaged over the devices."""
    total = 0.0
    for dev, ops in t.devices.items():
        merged = _busy(t, dev) if match is None else _union(
            _select(ops, match))
        total += sum(e - s for s, e in merged)
    return total / len(t.devices) * 1e-9


def idle_share(t: Trace) -> float:
    return 1.0 - busy_s(t) / t.window_s


def op_seconds(t: Trace, match: re.Pattern) -> tuple[float, int]:
    """Device seconds of the ops whose names ``match`` finds, summed over
    the window and averaged over devices; and how many such events all
    the devices ran."""
    secs, count = 0.0, 0
    for ops in t.devices.values():
        for s, e, _ in _select(ops, match):
            secs += (e - s) * 1e-9
            count += 1
    return secs / len(t.devices), count


def _self_times(ops):
    """(name, self seconds) of properly nested sorted events."""
    stack: list = []  # [end, name, child time]
    out = []
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            end, nm, kids, st = stack.pop()
            out.append((nm, (end - st - kids) * 1e-9))
            if stack:
                stack[-1][2] += end - st
        stack.append([e, name, 0, s])
    while stack:
        end, nm, kids, st = stack.pop()
        out.append((nm, (end - st - kids) * 1e-9))
        if stack:
            stack[-1][2] += end - st
    return out


def top_ops(t: Trace, n: int = 10, width: int = 160) -> list:
    """The ``n`` ops that took most device self time in the window,
    averaged over devices: [[name (cut to ``width``), seconds], ...]."""
    acc: collections.Counter = collections.Counter()
    for ops in t.devices.values():
        for name, secs in _self_times(ops):
            acc[name[:width]] += secs / len(t.devices)
    return [[k, v] for k, v in acc.most_common(n)]


def _host_activity(t: Trace, times: list) -> list:
    """For each of the ascending ``times``, the innermost benchmark span
    running on the host then: the one that started last among those
    that span it."""
    spans = [sp for sp in t.spans if sp[2] != WINDOW_SPAN]
    heap: list = []  # (-start, end, name) of spans started so far
    out, k = [], 0
    for at in times:
        while k < len(spans) and spans[k][0] <= at:
            heapq.heappush(heap, (-spans[k][0], spans[k][1], spans[k][2]))
            k += 1
        # a span that ended before ``at`` spans no later time either
        while heap and heap[0][1] < at:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else "outside any benchmark span")
    return out


def idle_gaps(t: Trace, n: int = 10) -> list:
    """Idle time in the window between device operations, summed by what
    the host was doing at the middle of each gap, averaged over devices:
    the ``n`` largest as [[host span, seconds], ...]."""
    lo, hi = t.window
    acc: collections.Counter = collections.Counter()
    for dev in t.devices:
        edges = [lo] + [x for iv in _busy(t, dev) for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        names = _host_activity(t, [(a + b) / 2 for a, b in gaps])
        for (a, b), name in zip(gaps, names):
            acc[name] += (b - a) * 1e-9 / len(t.devices)
    return [[k, v] for k, v in acc.most_common(n)]
