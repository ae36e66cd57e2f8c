"""A run with its timed path broken underneath comes out not correct.

The harness's look for a chip is skipped; everything else runs as on the
chip, at a size a CPU holds, in bfloat16 as the configurations state,
against the limits the cells hold.  Each cell is run sound first (it
must be correct), then once for each fault it can have.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import faults  # noqa: E402
import tinytree  # noqa: E402
from chipbench import harness  # noqa: E402

SEED = 2**31 + 5


def _run(tmp_path, cell):
    root, bench = tinytree.make(tmp_path)
    return harness.run_cell(harness.Files(root, bench), cell, SEED, 0.5,
                            False, require_chip=False, compile_cache=False)


@pytest.mark.parametrize("cell", ["olmo1b-train-2k", "olmo1b-decode-chat"])
def test_a_sound_run_is_correct(tmp_path, cell):
    res = _run(tmp_path, cell)
    assert res["correct"] is True, res["checked"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(tmp_path, monkeypatch, fault):
    monkeypatch.setattr(*faults.plant(fault))
    res = _run(tmp_path, "olmo1b-train-2k")
    assert res["correct"] is False, res["checked"]


def test_an_altered_token_is_not_correct(tmp_path, monkeypatch):
    # the last position at which the longest request is served a token
    gen = harness.Files(tinytree.ROOT).module("generators", "lognormal_chat")
    at = max(p + n for grp in gen.sizes(tinytree.MIX["azure-conv"])
             for p, n in grp) - 2
    monkeypatch.setattr(*faults.plant("token_altered", position=at))
    res = _run(tmp_path, "olmo1b-decode-chat")
    assert res["correct"] is False, res["checked"]


def test_prefill_sound_and_broken_on_four_devices(tmp_path):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "prefill_faults_driver.py"),
         str(tmp_path), "exchange_left_out", "answer_altered"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    runs = {r["fault"]: r for r in map(json.loads,
                                        proc.stdout.strip().splitlines())}
    assert runs[None]["correct"] is True, runs[None]
    for fault in ("exchange_left_out", "answer_altered"):
        assert runs[fault]["correct"] is False, runs[fault]
