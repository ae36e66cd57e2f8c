"""``control.py`` judges every reading as a run does: each number beside
the cell's limit, and ``correct`` from the harness's own verdict, so a
control reading over its limit reports ``correct: false``."""

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import control  # noqa: E402
import tinytree  # noqa: E402
from chipbench import harness  # noqa: E402


def test_program_and_control_readings_carry_the_harness_verdict(tmp_path):
    root, bench = tinytree.make(tmp_path)
    # At this width the float8 control's widest gap reads about 0.1 and
    # the program's under 0.01; the chip's limit is set from full-size
    # readings, so this test sets one between the two.
    cell = bench / "cells/olmo1b-decode-chat.json"
    limits = json.loads(cell.read_text())
    limits["limits"]["token_gap"] = 0.05
    cell.write_text(json.dumps(limits))
    out = control.program_reading(harness.Files(root, bench),
                                  "olmo1b-decode-chat", 2**31 + 5, 0.2,
                                  control=True)
    prog, ctl = out["program"], out["control"]
    assert set(prog["checked"]) == set(ctl["checked"]) == {"token_gap"}
    for reading in (prog, ctl):
        gap = reading["checked"]["token_gap"]
        assert gap["limit"] == 0.05
        assert reading["correct"] is harness.verdict(
            0, {"token_gap": (gap["value"], gap["limit"])})
    assert prog["correct"] is True, prog
    assert ctl["correct"] is False, ctl
