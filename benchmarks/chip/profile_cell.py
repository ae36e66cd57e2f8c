"""One run of one cell with the program's own spans in its trace, and what
the program's scopes say about the window.

    python3 benchmarks/chip/profile_cell.py --workload <cell> --seed <n> \
        --seconds <s> [--trace 0|1] [--sink 0|1] [--out <file.json>]

It runs the cell as ``run.py`` does (``chipbench.harness.run_cell``),
with two differences.  ``--sink 1`` switches the program's profiler sink
on (``repro.obs.trace.enable_profiler``), so that the spans the program
opens (``serve/run``, ``serve/step`` and its phases ``serve/feed``,
``serve/dispatch``, ``serve/fetch``, ``serve/emit``) are host TraceMes
on the device's clock; and the trace reduction keeps the program's spans
beside the benchmark's, so that the breakdown names each idle gap by the
program's innermost span.  With ``--trace 1`` it adds, under
``profile``: device self ms per step by scope chain (``chipbench.scopes``)
and the share of busy time that no scope holds; the ten top ops, each
with its scope; the forty instructions with most self time, each with
its scope and result shape; idle ms per step by span; and the
``ficco/exchange`` scope's ms per step, whole and in its kernel alone.  Every run adds the window's step time and, for a
serving cell, the gap between tokens.  It is a diagnostic for what the
benchmark's readers should read: the benchmark's command does not run
it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

PROGRAM_SPANS = ("serve/", "train/", "overlap/")


def _path(chain: tuple) -> str:
    """A scope chain as one path: ``layers/mlp/up_gate/ficco/exchange``."""
    inner = [s for s in chain if not any(o.startswith(s + "/")
                                         for o in chain)]
    return "/".join(inner) or "no scope"


def profile(t, table: dict, steps: int, result: dict) -> dict:
    from chipbench import scopes, trace

    by_name, joined = scopes.self_seconds(t, table)
    secs = scopes.by_chain(by_name, table)
    busy = sum(secs.values())
    by_scope = {_path(ch): 1e3 * s / steps
                for ch, s in sorted(secs.items(), key=lambda kv: -kv[1])
                if ch is not None}
    unscoped = secs.get(None, 0.0) + secs.get((), 0.0)
    exchange = sum(s for ch, s in secs.items()
                   if ch and "ficco/exchange" in ch)
    # the kernel alone: the scope also holds the slicing of its chunks
    kernel = sum(s for n, s in by_name.items() if n is not None
                 and "ficco/exchange" in table[n][1]
                 and table[n][2] == "custom-call")
    top = []
    for name, s in trace.top_ops(t, n=10, width=10**6):
        hit = scopes.join(name, table)
        top.append([name[:160], s,
                    "not joined" if hit is None else _path(table[hit][1])])
    ranked = sorted(((s, n) for n, s in by_name.items() if n is not None),
                    reverse=True)[:40]
    return {
        "steps": steps,
        "busy_s": busy,
        "joined_share": joined,
        "scoped_share": 1.0 - unscoped / busy if busy else 0.0,
        "ms_per_step_by_scope": by_scope,
        "ficco_exchange_ms_per_step": (1e3 * exchange / steps
                                       if exchange else None),
        "ficco_exchange_kernel_ms_per_step": (1e3 * kernel / steps
                                              if kernel else None),
        "top_ops": top,
        "ms_per_step_by_instruction": [
            [n, 1e3 * s / steps, _path(table[n][1]),
             scopes.result_dims(table[n][0])] for s, n in ranked],
        "idle_ms_per_step_by_span": {
            name: 1e3 * s / steps
            for name, s in result["breakdown"]["idle_gaps"]},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--sink", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from chipbench import harness, scopes, trace

    root = pathlib.Path.cwd()
    sys.path.insert(0, str(root / "src"))
    from repro.obs import trace as program_trace

    seen: dict = {}
    load, make_loop = trace.load, harness.Files.make_loop

    def keep(path):
        seen["trace"] = load(path)
        return seen["trace"]

    def recorded_loop(self, *a, **k):
        loop = make_loop(self, *a, **k)
        window = loop.window

        def recorded(seconds):
            seen["records"] = window(seconds)
            return seen["records"]

        loop.window = recorded
        return loop

    trace.load = keep
    trace.SPAN_PREFIX = (trace.SPAN_PREFIX,) + PROGRAM_SPANS
    harness.Files.make_loop = recorded_loop
    if args.sink:
        program_trace.enable_profiler()
    result = harness.run_cell(harness.Files(root=root), args.workload,
                              args.seed, args.seconds, bool(args.trace),
                              t_start=T_START)
    program_trace.disable_profiler()

    rec = seen["records"]
    result["sink"] = bool(args.sink)
    result["window"] = {"step_ms": 1e3 * rec["elapsed"] / rec["steps"],
                        "steps": rec["steps"], **rec["metrics"]}
    if args.trace:
        table = scopes.table_for(args.workload, root)
        result["profile"] = profile(seen["trace"], table, rec["steps"],
                                    result)
    line = json.dumps(result)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
