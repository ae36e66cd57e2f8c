"""A family's numbers, pinned: its weights from a seed, the work its
counters count, and its reference's gaps with the control's.

Each ``pins/<family>.json`` holds numbers recorded from the family's
code: a digest of every weight leaf of each configuration's tiny cut
(``tinytree``) for one seed; the work of each counter at a cell's own
sizes; and, on the tiny cut, the gaps of chosen tokens below the
reference's best and of the float8 control's first tokens.  Code that
moves or reshapes a family has to give the same numbers.  The gaps are
sums of float32 products, whose order the CPU's threads can change in
the last bits: they are held to 1e-5, and the weights and the work
exactly.
"""

import functools
import hashlib
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import tinytree  # noqa: E402
from chipbench import harness  # noqa: E402
from chipbench.family import seed_key  # noqa: E402

PINS = {p.stem: json.loads(p.read_text())
        for p in sorted((HERE / "pins").glob("*.json"))}
FILES = harness.Files(BENCH.parents[1])


def _config(name: str, tiny: bool) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    if tiny:
        cut = BENCH / tinytree.TINY / "configs" / f"{name}.json"
        cfg = tinytree.merged(cfg, json.loads(cut.read_text()))
    return cfg


def _cases(part: str) -> list:
    return [(fam, name) for fam, pins in PINS.items()
            for name in pins[part]]


@pytest.mark.parametrize("family,config", _cases("weights"))
def test_weights_from_the_seed_are_pinned(family, config):
    pins = PINS[family]
    cfg = _config(config, tiny=True)
    fam = FILES.family(cfg)
    assert cfg["family"] == family
    make = functools.partial(fam.program_params, fam.dims(cfg))
    params = jax.jit(make)(seed_key(pins["seed"]))
    got = {jax.tree_util.keystr(k):
           hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == pins["weights"][config]


@pytest.mark.parametrize("family", sorted(PINS))
def test_work_of_each_counter_is_pinned(family):
    for w in PINS[family]["work"]:
        cfg = _config(w["config"], tiny=False)
        fam = FILES.family(cfg)
        got = getattr(fam, w["counter"])(fam.dims(cfg), *w["args"])
        assert (got.flops, got.bytes) == (w["flops"], w["bytes"]), w


@pytest.mark.parametrize("family,config", _cases("gaps"))
def test_reference_and_control_gaps_are_pinned(family, config):
    pins = PINS[family]
    want = pins["gaps"][config]
    cfg = _config(config, tiny=True)
    fam = FILES.family(cfg)
    m = fam.dims(cfg)
    rows, width = want["tokens"]
    toks = np.random.default_rng(3).integers(0, m.vocab_size, (rows, width),
                                             dtype=np.int32)
    chosen = (toks * 7 + 1) % m.vocab_size
    gaps, cgaps = fam.token_gaps(
        m, pins["seed"], [jnp.asarray(toks[i:i + 1]) for i in range(rows)],
        [jnp.asarray(chosen[i:i + 1]) for i in range(rows)], control=True)
    for got, key in ((gaps, "gap"), (cgaps, "control")):
        np.testing.assert_allclose(
            np.stack([np.asarray(g).ravel() for g in got]),
            np.asarray(want[key]), rtol=0, atol=1e-5, err_msg=key)
