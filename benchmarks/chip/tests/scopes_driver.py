"""Compile a tiny cell's timed program twice, with the program's named
scopes and with ``jax.named_scope`` made a no-op; print one JSON line:
whether the two optimized HLO texts are the same once their metadata is
stripped, whether it is the program the cell's own loop compiles, and
the scopes the program's text holds.  ``test_chipbench_scopes`` starts
it in a process of its own, because the device count (four, for the
prefill cell's mesh) is fixed when JAX starts.

    python3 scopes_driver.py <tmp dir> <cell> [overlap backend]
"""

import contextlib
import json
import os
import pathlib
import re
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import tinytree  # noqa: E402

METADATA = re.compile(r", metadata=\{[^}]*\}")


def strip(text: str) -> list:
    """A compiled module's lines without metadata: each instruction's
    ``metadata={...}``, and the tables of source files, functions and
    stack frames that the module's text opens with."""
    out, tables = [], False
    for line in METADATA.sub("", text).splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            tables = True
        elif line.startswith(("%", "ENTRY")):
            tables = False
        if not tables:
            out.append(line)
    return out


def texts(files, cell: str) -> tuple[str, str]:
    """The cell's compiled program with its scopes, and without them."""
    import jax

    from chipbench import scopes

    scoped = scopes.compiled_text(*scopes.program(files, cell))
    named_scope = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        plain = scopes.compiled_text(*scopes.program(files, cell))
    finally:
        jax.named_scope = named_scope
    return scoped, plain


def canonical(lines: list) -> list:
    """``lines`` with each instruction and computation renamed by the
    order it first appears in.  The converter from MLIR to HLO names an
    instruction after the last part of its location, so a scope can
    change a name (``%shard_map.72`` becomes ``%exchange.12``) and no
    more."""
    names: dict = {}

    def rename(m):
        return names.setdefault(m.group(1), f"%v{len(names)}")

    return [re.sub(r"%([\w.\-]+)", rename, line) for line in lines]


def loop_text(files, cell: str) -> str:
    """The program the cell's own loop times, compiled for the arguments
    it is called with."""
    import jax.numpy as jnp

    from chipbench import scopes

    loop = files.make_loop(files.cell(cell), 2**31 + 3)
    loop.setup()
    if hasattr(loop, "engine"):
        eng = loop.engine
        tok = jnp.zeros((eng.batch, 1), jnp.int32)
        return scopes.compiled_text(
            eng.step_fn, (eng.params, eng.cache, tok, jnp.int32(0)))
    return scopes.compiled_text(loop.fn, (loop.params, loop.inputs[0]),
                                loop.mesh)


def compare(files, cell: str) -> dict:
    from chipbench import scopes

    scoped, plain = texts(files, cell)
    a, b = strip(scoped), strip(plain)
    return {"cell": cell, "identical": a == b,
            "canonical": canonical(a) == canonical(b), "lines": len(a),
            "renamed": sum(x != y for x, y in zip(a, b)),
            "as_the_loop_runs": strip(loop_text(files, cell)) == a,
            "scopes": sorted({s for t in scopes.parse(scoped).values()
                              for s in t[1]}),
            "plain_scopes": sorted({s for t in scopes.parse(plain).values()
                                    for s in t[1]})}


def tree(tmp: pathlib.Path, cell: str, backend: str = ""):
    """The tiny tree, with ``cell``'s configuration on the overlap
    ``backend`` where one is given."""
    from chipbench import harness

    root, bench = tinytree.make(tmp)
    files = harness.Files(root, bench)
    if backend:
        name = files.cell(cell)["entry"]["config"]
        path = bench / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["overlap"]["backend"] = backend
        path.write_text(json.dumps(cfg))
    return files


def main():
    files = tree(pathlib.Path(sys.argv[1]), *sys.argv[2:])
    print(json.dumps(compare(files, sys.argv[2])), flush=True)


if __name__ == "__main__":
    main()
