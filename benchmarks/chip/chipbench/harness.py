"""One run of one cell: find its files by name, set up, measure, check.

Everything that belongs to one cell is found by name under the
benchmark's directory, so a cell, a configuration, a mix or a per-layer
metric is added by adding files and entries, never by editing this one:

* ``BENCHMARK.json`` (the repository's root): the cell, its chips, and
  which end-to-end and per-layer metrics it reports;
* ``configs/<config>.json``: the sizes as run, the overlap mode, the mesh,
  and the ``family`` the sizes belong to;
* ``families/<family>.py``: the family's sizes, the program's config
  object, weights from the seed, the work a step requires and the plain
  reference (``chipbench.family``);
* ``traffic/<traffic>.json``: the mix, a data file that names its
  ``generator`` and its ``loop``;
* ``generators/<generator>.py``: ``batch(mix, vocab, seed, i)``, the
  inputs of batch ``i`` made from the seed;
* ``loops/<loop>.py``: ``Loop(ctx)``, which drives the program's timed
  path, and ``program(ctx)``, the program it times (``chipbench.loops``);
* ``cells/<cell>.json``: the limits of the numbers that decide ``correct``;
* ``metrics/<metric>.py``: a reader, ``read(run) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time
from typing import Any, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


class NoChip(SystemExit):
    """The machine lacks the accelerator or the chips the cell asks for."""


@dataclasses.dataclass
class Files:
    """Where a benchmark's files live: ``root`` holds BENCHMARK.json and
    ``bench`` the configs, families, traffic, generators, loops, cells
    and metrics directories."""

    root: pathlib.Path
    bench: pathlib.Path = BENCH_DIR

    def spec(self) -> dict:
        return json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, sub: str, name: str) -> dict:
        return json.loads((self.bench / sub / f"{name}.json").read_text())

    def cell(self, name: str) -> dict:
        spec = self.spec()
        cells = [w for w in spec["workloads"] if w["name"] == name]
        if len(cells) != 1:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        w = cells[0]
        return {
            "entry": w,
            "config": self._json("configs", w["config"]),
            "traffic": self._json("traffic", w["traffic"]),
            "limits": self._json("cells", name),
            "end_to_end": [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])],
        }

    def module(self, sub: str, name: str):
        """The Python file ``<sub>/<name>.py``, loaded as a module."""
        path = self.bench / sub / f"{name}.py"
        if not path.is_file():
            raise SystemExit(f"no {sub} file {name!r}: {path}")
        key = "".join(ch if ch.isalnum() else "_" for ch in name)
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{sub}_{key}", path)
        mod = importlib.util.module_from_spec(spec)
        # A dataclass looks its module up by name while it is made.
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        return self.module("metrics", metric).read

    def family(self, config: dict):
        """The module of the family a configuration file names."""
        name = config.get("family")
        if name is None:
            raise SystemExit("no families file: the configuration names "
                             "no family")
        return self.module("families", name)

    def context(self, c: dict, seed: int, traced: bool = False,
                devices=None):
        """What the loop of cell ``c`` (from :meth:`cell`) reads: its
        files, its mix's generator and its configuration's family."""
        from chipbench import loops

        mix = c["traffic"]
        return loops.Context(c["entry"]["name"], c["config"], mix,
                             c["limits"], seed, c["entry"]["chips"],
                             self.module("generators", mix["generator"]),
                             self.family(c["config"]), traced, devices)

    def make_loop(self, c: dict, seed: int, traced: bool = False):
        """The loop that drives cell ``c`` (from :meth:`cell`) for
        ``seed``."""
        loop = self.module("loops", c["traffic"]["loop"])
        return loop.Loop(self.context(c, seed, traced))


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads."""

    records: dict[str, Any]  # the loop's window records
    trace: Any  # chipbench.trace.Trace of the window
    peaks: Any  # chipbench.peaks.Peaks of the device
    chips: int


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: {devs[0].platform} {devs[0].device_kind!r} x{len(devs)}",
          file=sys.stderr, flush=True)
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX sees {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX sees {len(devs)}")
    return info


def use_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def verdict(failed: int, checks: dict) -> bool:
    """``correct``: nothing failed, and every number is within its limit."""
    return failed == 0 and all(v <= lim for v, lim in checks.values())


def run_cell(files: Files, cell: str, seed: int, seconds: float,
             traced: bool, *, require_chip: bool = True,
             compile_cache: bool = True,
             t_start: Optional[float] = None) -> dict:
    """Set up, measure for ``seconds``, check; returns the result line."""
    t_start = time.perf_counter() if t_start is None else t_start
    c = files.cell(cell)
    chips = c["entry"]["chips"]
    device = device_info(chips, require_chip)

    import jax

    from chipbench import peaks, trace

    if compile_cache:
        use_compile_cache(files.root)
    sys.path.insert(0, str(files.root / "src"))
    loop = files.make_loop(c, seed, traced)
    loop.setup()
    setup_s = time.perf_counter() - t_start

    tr = None
    if traced:
        # The Python tracer, on by default, would record every Python
        # call of the host loop: it slows the loop it measures and swells
        # the trace.  The benchmark's spans are host TraceMes and stay.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory() as logdir:
            jax.profiler.start_trace(logdir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                rec = loop.window(seconds)
            jax.profiler.stop_trace()
            tr = trace.load(trace.find_xplane(logdir))
    else:
        rec = loop.window(seconds)
    device["memory_peak_bytes"] = memory_peak(chips)
    loop.release()
    checks = loop.check()

    if traced:
        run = Run(rec, tr, peaks.peaks_for(device["kind"]), chips)
        metrics = {}
        for m in c["per_layer"]:
            value = files.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        from chipbench import readers

        bound = readers.roofline_bound(run)
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
    else:
        measured = dict(rec["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}

    correct = verdict(rec["failed"], checks)
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"
    for name, (v, lim) in checks.items():
        print(f"{tag} check {name}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    result = {
        "correct": correct, "attempted": rec["attempted"],
        "failed": rec["failed"], "metrics": metrics, "device": device,
    }
    if traced:
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
        result["roofline_bound"] = bound
    result["checked"] = {name: {"value": v, "limit": lim}
                         for name, (v, lim) in checks.items()}
    return result
