"""A copy of the benchmark's files with every configuration cut to a size
a CPU test can run, the mixes shortened, and the limits as the cells
have them.

The training cell (``olmo1b-train-2k``) is not in BENCHMARK.json while
the program's step is at fault at its batch (PERF.md, Open questions);
its files are kept, and the copy adds it back by entries alone, as the
PR that restores it will."""

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]

_OLMO = dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=4,
             head_dim=64, d_ff=512, vocab_size=512)
MODEL = {
    "olmo-1b-stage8": _OLMO,
    "olmo-1b": _OLMO,
    "yi-9b": dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=2,
                  head_dim=64, d_ff=512, vocab_size=512),
}
MIX = {
    "lm-2k": dict(batch=4, seq_len=32),
    "azure-conv": dict(
        batch=4, groups=2, cache_len=24,
        prompt_len={"median": 8, "sigma": 0.5},
        new_tokens={"median": 4, "sigma": 1.0}),
    "prefill-4k": dict(batch=4, seq_len=32),
}


def make(root: pathlib.Path, dtype: str = "bfloat16"):
    """Write the tiny tree under ``root``; returns (root, bench)."""
    root = pathlib.Path(root)
    bench = root / "bench"
    for sub in ("configs", "traffic", "cells"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "generators", "loops"):
        shutil.copytree(BENCH / sub, bench / sub, dirs_exist_ok=True)
    spec = with_train_cell(json.loads((ROOT / "BENCHMARK.json").read_text()))
    for c in spec["configs"]:
        d = json.loads((ROOT / c["file"]).read_text())
        d["model"].update(MODEL[c["name"]], dtype=dtype)
        (bench / "configs" / f"{c['name']}.json").write_text(json.dumps(d))
    for w in spec["workloads"]:
        t = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        t.update(MIX[w["traffic"]])
        (bench / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(t))
        shutil.copy(BENCH / "cells" / f"{w['name']}.json", bench / "cells")
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
