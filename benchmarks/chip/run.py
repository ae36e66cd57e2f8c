"""The on-chip benchmark: one run of one cell, one JSON result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.  The last lines on standard error, and the
result's ``checked`` key, give each number compared beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    files = harness.Files(root=pathlib.Path.cwd())
    result = harness.run_cell(files, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
