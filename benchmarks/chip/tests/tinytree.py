"""A copy of the benchmark's files with every configuration cut to a size
a CPU test can run, the mixes shortened, and the limits as the cells
have them.

Each configuration and each mix has its tiny cut in a file of the same
name, ``tiny/configs/<config>.json`` and ``tiny/traffic/<mix>.json``,
whose values replace the file's own (a group of values, such as a
configuration's ``model``, key by key).  A configuration or mix added to
BENCHMARK.json brings its tiny file beside it; nothing here names one.

The training cell (``olmo1b-train-2k``) is not in BENCHMARK.json while
the program's step is at fault at its batch (PERF.md, Open questions);
its files are kept, and the copy adds it back by entries alone, as the
PR that restores it will."""

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
TINY = pathlib.Path("tests") / "tiny"  # under a benchmark's directory


def _tiny(bench: pathlib.Path, kind: str) -> dict:
    return {p.stem: json.loads(p.read_text())
            for p in sorted((bench / TINY / kind).glob("*.json"))}


# The tiny mixes, for tests that size their checks by them.
MIX = _tiny(BENCH, "traffic")


def merged(base: dict, cut: dict) -> dict:
    """``base`` with the values of ``cut``; a group in both is merged key
    by key."""
    out = dict(base)
    for k, v in cut.items():
        both = isinstance(v, dict) and isinstance(base.get(k), dict)
        out[k] = merged(base[k], v) if both else v
    return out


def missing(source: pathlib.Path = ROOT) -> list:
    """The tiny files that the configurations and mixes of ``source``'s
    BENCHMARK.json (with the training cell) lack."""
    spec = with_train_cell(
        json.loads((source / "BENCHMARK.json").read_text()))
    bench = source / spec["paths"][0] / TINY
    want = ([bench / "configs" / f"{c['name']}.json"
             for c in spec["configs"]]
            + [bench / "traffic" / f"{w['traffic']}.json"
               for w in spec["workloads"]])
    return sorted({str(p.relative_to(source)) for p in want
                   if not p.is_file()})


def make(root: pathlib.Path, source: pathlib.Path = ROOT):
    """Write the tiny tree of the benchmark at ``source`` (a checkout's
    root) under ``root``; returns (root, bench)."""
    root = pathlib.Path(root)
    spec = with_train_cell(
        json.loads((source / "BENCHMARK.json").read_text()))
    src = source / spec["paths"][0]
    bench = root / "bench"
    shutil.copytree(src, bench, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    lacking = missing(source)
    if lacking:
        raise FileNotFoundError(f"no tiny cut: {', '.join(lacking)}")
    models, mixes = _tiny(src, "configs"), _tiny(src, "traffic")
    for c in spec["configs"]:
        d = json.loads((src / "configs" / f"{c['name']}.json").read_text())
        (bench / "configs" / f"{c['name']}.json").write_text(
            json.dumps(merged(d, models[c["name"]])))
    for w in spec["workloads"]:
        t = json.loads(
            (src / "traffic" / f"{w['traffic']}.json").read_text())
        (bench / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(merged(t, mixes[w["traffic"]])))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, bench


def with_train_cell(spec: dict) -> dict:
    """``spec`` with the training cell's entries, if it lacks them."""
    if any(w["name"] == "olmo1b-train-2k" for w in spec["workloads"]):
        return spec
    spec["configs"].append({
        "name": "olmo-1b-stage8", "source": "arXiv:2402.00838",
        "file": "benchmarks/chip/configs/olmo-1b-stage8.json",
        "reduced": ["num_layers"], "why": "one pipeline stage"})
    spec["workloads"].append({
        "name": "olmo1b-train-2k", "config": "olmo-1b-stage8",
        "traffic": "lm-2k", "chips": 1, "why": "train steps"})
    spec["end_to_end"].append({
        "name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["olmo1b-train-2k"]})
    return spec
