"""Run the tiny prefill cell on four CPU devices: sound, then with each
fault planted; print one JSON line per run.  ``test_chipbench_faults``
starts it in a process of its own, because the device count is fixed
when JAX starts.

    python3 prefill_faults_driver.py <tmp dir> [fault ...]
"""

import json
import os
import pathlib
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import faults  # noqa: E402
import tinytree  # noqa: E402
from chipbench import harness  # noqa: E402


def main():
    root, bench = tinytree.make(pathlib.Path(sys.argv[1]))
    files = harness.Files(root, bench)
    for fault in [None] + sys.argv[2:]:
        undo = None
        if fault:
            mod, attr, new = faults.plant(fault)
            undo = (mod, attr, getattr(mod, attr))
            setattr(mod, attr, new)
        try:
            res = harness.run_cell(files, "yi9b-prefill-tp4", 2**31 + 5, 0.5,
                                   False, require_chip=False,
                                   compile_cache=False)
        finally:
            if undo:
                setattr(*undo)
        print(json.dumps({"fault": fault, "correct": res["correct"],
                          "checked": res["checked"]}), flush=True)


if __name__ == "__main__":
    main()
