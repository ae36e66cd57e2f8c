"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 11 12 ... --control-seeds 21 22 23 [--fault half_batch] \
        [--out control.jsonl]

In one process, for each of ``--seeds``: set the cell up as a run does,
drive its timed path through a short window (at least one step, one
batch or one call), and compare with the reference: the program's
readings, whose largest sets a limit's lower end.  For each of
``--control-seeds``: the same numbers with the reference computed in
float8 put in the program's place (the control), whose smallest sets the
upper end.  A serving cell reads the control's first token at the
positions, prompts and served tokens of the program's own run, so its
control seeds are among ``--seeds``.  ``--fault half_batch`` (training
cells) instead runs the program with half of each batch left out, the
mean taken over the rest.
Prints one JSON line per reading, with each number beside the cell's
limit and ``correct`` as a run would decide it: the control has to come
out not correct.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def _judged(failed: int, checks: dict) -> dict:
    """A reading as a run reports it: each number beside its limit, and
    ``correct`` as the harness decides it."""
    from chipbench import harness

    return {"correct": harness.verdict(failed, checks),
            "checked": {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}}


def program_reading(files, cell: str, seed: int, seconds: float,
                    fault=None, control: bool = False) -> dict:
    """The program's reading for ``seed``; with ``control`` (a serving
    cell), also the control's, judged against the same limits:
    ``{"program": ..., "control": ...}``."""
    import jax

    loop = files.make_loop(files.cell(cell), seed)
    if fault == "half_batch":
        half = loop.ctx.mix["batch"] // 2
        orig = loop._batch
        loop._batch = lambda i: orig(i)[:half]
    loop.setup()
    if fault == "half_batch":
        loop._batch = orig  # the reference follows the whole batches
    rec = loop.window(seconds)
    loop.release()
    checks = loop.check(control=control)
    prog = {k: v for k, v in checks.items() if not k.startswith("control:")}
    out = {"program": _judged(rec["failed"], prog)}
    if control:
        out["control"] = _judged(0, {
            k[len("control:"):]: v for k, v in checks.items()
            if k.startswith("control:")})
    del loop
    jax.clear_caches()
    return out


def control_reading(files, cell: str, seed: int) -> dict:
    """A training cell's numbers with the float8 reference in the
    program's place."""
    from chipbench import loops

    c = files.cell(cell)
    ctx = files.make_loop(c, seed).ctx
    batches = [ctx.batch(i) for i in range(3)]
    opt = c["config"]["optimizer"]
    block = c["traffic"].get("reference_block", 1)
    ref = ctx.family.train_reference(ctx.dims, seed, batches, opt,
                                     block=block)
    ctl = ctx.family.train_reference(ctx.dims, seed, batches, opt,
                                     quant="fp8", block=block)

    class Prog:
        losses = ctl["losses"]
        grad_norms = ctl["grad_norms"]
        change_norms = ctl["change_norms"]

    return {"control": _judged(0, loops.train_numbers(
        Prog, ref, c["limits"]["limits"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", choices=("half_batch",))
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from chipbench import harness

    files = harness.Files(root=pathlib.Path.cwd())
    chips = files.cell(args.workload)["entry"]["chips"]
    harness.device_info(chips, require_chip=True)
    harness.use_compile_cache(files.root)
    sys.path.insert(0, str(files.root / "src"))
    out = open(args.out, "a") if args.out else None
    training = files.cell(args.workload)["traffic"]["loop"] == "train"
    jobs = [("program" if not args.fault else args.fault, s)
            for s in args.seeds]
    if training:
        jobs += [("control", s) for s in args.control_seeds]
    for kind, seed in jobs:
        t = time.perf_counter()
        if kind == "control":
            nums = control_reading(files, args.workload, seed)
        else:
            # A serving cell's control is read at the program's own
            # positions, prompts and served tokens.
            nums = program_reading(
                files, args.workload, seed, args.seconds, args.fault,
                control=not training and seed in args.control_seeds)
        secs = time.perf_counter() - t
        for who, reading in nums.items():
            line = json.dumps({
                "cell": args.workload,
                "kind": kind if who == "program" else who,
                "seed": seed, **reading, "seconds": secs})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
