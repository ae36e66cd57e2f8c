"""Which of the program's scopes each device op of a traced window ran in.

A TPU trace names an op's event by its HLO instruction as the compiler
prints it (``%fusion.233 = bf16[...] fusion(bf16[...] %fusion.232),
kind=...``) and carries no scope.  The program marks its layers with
``jax.named_scope`` (``layers``, ``attn/qkv``, ``mlp/up_gate``,
``ficco/exchange``, ...), which the compiler keeps in each instruction's
``metadata={op_name="jit(prefill)/layers/while/body/mlp/up_gate/..."}``.
So the cell's program is compiled once more after the window, as its
loop builds it (:func:`program`), with the metadata in the compile
cache's key so that the text holds this program's own scopes; and each
event is joined to the instruction of the same name whose signature (its
result type and operand names) is the event's.  The compile is
deterministic, so the names are those of the program that ran; an event
that joins nothing, such as an op of an eager call, counts as unscoped,
and a reader reads None where under half the busy time joins.

An instruction with no scope of its own (a copy the compiler put in)
takes that of the loop or call whose body holds it.

The cell is the one the benchmark's command line names (``--workload``,
as ``run.py`` takes it), with its files under the working directory, as
``run.py`` finds them.  Without one, or where the program has none of
the scopes, the readers read None.
"""

from __future__ import annotations

import argparse
import collections
import functools
import pathlib
import re
import sys
from typing import Optional

from chipbench import trace

# The program's scopes: top-level names, and the children each may have.
TOP = ("layers", "attn", "kv_cache", "mlp", "ficco", "norm", "embed",
       "logits")
CHILDREN = {"attn": ("qkv", "core", "o"), "mlp": ("up_gate", "down"),
            "ficco": ("exchange", "gemm")}
# Below this share of the window's busy time joined to the compiled
# program, the program compiled here is not the one that ran.
MIN_JOINED = 0.5

_INSTR = re.compile(r"^\s*(?:ROOT )?%(?P<name>[^\s=]+) = ")
_COMP = re.compile(r"^(?:ENTRY )?%(?P<name>[^\s(]+) ")
_OP_NAME = re.compile(r', metadata=\{[^}]*?op_name="(?P<op>[^"]*)"')
_CALLS = re.compile(r"\b(?:condition|body|calls|to_apply|true_computation"
                    r"|false_computation)=%(?P<c>[^\s,)}]+)")
_BRANCHES = re.compile(r"branch_computations=\{(?P<cs>[^}]*)\}")


def chain(op_name: str) -> tuple:
    """The program scopes along an ``op_name`` path, outermost first:
    ``.../layers/while/body/mlp/up_gate/ficco/exchange/...`` gives
    ``("layers", "mlp", "mlp/up_gate", "ficco", "ficco/exchange")``.  Of
    the paths of ops the compiler merged (``a/x;b/y``), the first."""
    out: list = []
    parts = op_name.split(";")[0].split("/")
    for i, part in enumerate(parts):
        if part in TOP:
            nxt = parts[i + 1] if i + 1 < len(parts) else None
            found = [part] + ([f"{part}/{nxt}"]
                              if nxt in CHILDREN.get(part, ()) else [])
            # an inlined call can repeat its caller's path
            out.extend(s for s in found if s not in out)
    return tuple(out)


def _close(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return -1


_OPERAND = re.compile(r"%[^\s,()]+")
_OPCODE = re.compile(r"\s*(?P<op>[\w\-]+)\(")


def _split(text: str):
    """(text up to the end of the result type, the rest) of an
    instruction's text, without a leading ``ROOT``; None where the text
    stops inside its result type."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    eq = text.find(" = ")
    if eq < 0:
        return None
    i = eq + 3
    i = _close(text, i) if text.startswith("(", i) else text.find(" ", i)
    return None if i < 0 else (text[:i], text[i:])


def signature(text: str) -> tuple[str, bool]:
    """An instruction's name, result type and operand names, the form to
    which the compiled module's text and a TPU trace's event name both
    reduce: ``%fusion.3 = bf16[4]{0}(%a, %b)``.  The trace prints each
    operand's type before its name and an async op's opcode as
    ``async-start``; the module's text prints neither.  With whether the
    text held all of it: a text cut short gives what it holds."""
    parts = _split(text)
    if parts is None:
        return text.strip(), False
    head, rest = parts
    opening = rest.find("(")
    if opening < 0:
        return head, False
    end = _close(rest, opening)
    names = _OPERAND.findall(rest[opening:end if end > 0 else None])
    return head + "(" + ", ".join(names) + (")" if end > 0 else ""), end > 0


def opcode(text: str) -> str:
    """An instruction's opcode as the module's text prints it."""
    parts = _split(text)
    m = _OPCODE.match(parts[1]) if parts else None
    return m.group("op") if m else ""


def _instructions(hlo_text: str):
    """(computation, text) of each instruction of a module's text; an
    attribute can run over lines (a kernel's ``frontend_attributes``)."""
    comp, cur = None, None
    for line in hlo_text.splitlines():
        if _INSTR.match(line):
            if cur is not None:
                yield cur
            cur = (comp, line)
        elif cur is not None and line != "}" and not _COMP.match(line):
            cur = (comp, cur[1] + "\n" + line)
        else:
            if cur is not None:
                yield cur
            cur = None
            c = _COMP.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group("name")
    if cur is not None:
        yield cur


def parse(hlo_text: str) -> dict:
    """``{instruction name: (signature, scope chain, opcode)}`` of an
    optimized HLO module's text."""
    own: dict = {}  # name -> (signature, op_name, computation)
    caller: dict = {}  # computation -> the instruction that calls it
    for comp, line in _instructions(hlo_text):
        name = _INSTR.match(line).group("name")
        op = _OP_NAME.search(line)
        own[name] = (signature(line)[0], op.group("op") if op else "",
                     comp, opcode(line))
        called = [c.group("c") for c in _CALLS.finditer(line)]
        for b in _BRANCHES.finditer(line):
            called += [s.strip().lstrip("%")
                       for s in b.group("cs").split(",")]
        for c in called:
            caller.setdefault(c, name)

    @functools.lru_cache(maxsize=None)
    def op_name_of(name: str) -> str:
        _, op, where, _ = own[name]
        if op or where not in caller or caller[where] == name:
            return op
        return op_name_of(caller[where])

    return {n: (sig, chain(op_name_of(n)), code)
            for n, (sig, _, _, code) in own.items()}


def join(event: str, table: dict) -> Optional[str]:
    """The instruction an event's text names, or None if no instruction
    of the table has that name and signature."""
    m = _INSTR.match(event)
    name = m.group("name") if m else None
    hit = table.get(name)
    if hit is None:
        return None
    sig, whole = signature(event)
    if whole:
        return name if sig == hit[0] else None
    # the trace cut the text short: compare what it holds
    return name if hit[0].startswith(sig) else None


def self_seconds(t: trace.Trace, table: dict) -> tuple[dict, float]:
    """Device self seconds in the window by instruction, averaged over
    the devices (``None`` holds the ops that join none); and the share of
    busy time that joined the table."""
    acc: collections.Counter = collections.Counter()
    for ops in t.devices.values():
        found: dict = {}
        for name, secs in trace._self_times(ops):
            if name not in found:
                found[name] = join(name, table)
            acc[found[name]] += secs / len(t.devices)
    total = sum(acc.values())
    joined = total - acc.get(None, 0.0)
    return dict(acc), (joined / total if total > 0 else 0.0)


def by_chain(secs: dict, table: dict) -> dict:
    """Self seconds by instruction summed by scope chain (None: unjoined)."""
    out: collections.Counter = collections.Counter()
    for name, s in secs.items():
        out[None if name is None else table[name][1]] += s
    return dict(out)


def result_dims(sig: str) -> str:
    """The dimensions of an instruction's result, ``"16,4,2048"`` from
    ``%x = bf16[16,4,2048]{...} ...``; "" for a tuple or a scalar."""
    m = re.match(r"[^=]+ = \w+\[(?P<d>[\d,]*)\]", sig)
    return m.group("d") if m else ""


def program(files, cell: str, devices=None):
    """The jitted callable the cell's loop times, its arguments' shapes
    (with their shardings) and the mesh to compile it under, as the loop
    module's own ``program(ctx)`` builds them (a mesh of ``devices``
    where given); None for a loop that has no ``program``."""
    c = files.cell(cell)
    loop = files.module("loops", c["traffic"]["loop"])
    if not hasattr(loop, "program"):
        return None
    return loop.program(files.context(c, 0, devices=devices))


def compiled_text(fn, args, mesh=None) -> str:
    """``fn`` compiled for ``args``, as optimized HLO text with the
    program's metadata: the metadata is put in the compile cache's key,
    so a cached executable compiled from a program without it is not
    handed back."""
    import contextlib

    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        ctx = (jax.sharding.set_mesh(mesh) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update(key, before)


def cell(root: Optional[pathlib.Path] = None) -> Optional[dict]:
    """The files of the cell the command line names (``Files.cell``), or
    None."""
    name = cell_from_argv()
    if name is None:
        return None
    from chipbench import harness

    return harness.Files(root=root or pathlib.Path.cwd()).cell(name)


def cell_from_argv() -> Optional[str]:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    return ap.parse_known_args(sys.argv[1:])[0].workload


# Compiling a cell's program takes up to a minute on the chip, so each
# cell's table is made once a process and shared by its readers.
_TABLES: dict = {}


def table_for(cell: str, root: Optional[pathlib.Path] = None) -> dict:
    """The parsed compiled program of ``cell``."""
    if cell not in _TABLES:
        from chipbench import harness

        files = harness.Files(root=root or pathlib.Path.cwd())
        built = program(files, cell)
        _TABLES[cell] = {} if built is None else parse(compiled_text(*built))
    return _TABLES[cell]


def _times(run):
    """(table, self seconds by instruction, joined share) of the run,
    computed once and kept on it; None where the command line names no
    cell."""
    if not hasattr(run, "scope_times"):
        cell = cell_from_argv()
        if cell is None:
            run.scope_times = None
            return None
        try:
            table = table_for(cell)
        except Exception as e:  # a reader reads None; it never fails a run
            print(f"scopes: no compiled program for {cell}: {e!r}",
                  file=sys.stderr)
            table = _TABLES[cell] = {}
        run.scope_times = (table,) + self_seconds(run.trace, table)
    return run.scope_times


def ms_per_step(run, scope: str, extra=None) -> Optional[float]:
    """Device self ms per step of the window (a call or a decode step) in
    the ops whose scope chain holds ``scope``, or that ``extra(chain,
    signature)`` accepts, averaged over the devices.  None where the
    command line names no cell, the program has no ``scope``, or too
    little of the window joined the program compiled here."""
    got = _times(run)
    if got is None:
        return None
    table, secs, joined = got
    if joined < MIN_JOINED or not any(scope in t[1] for t in
                                      table.values()):
        if joined < MIN_JOINED:
            print(f"scopes: only {joined:.1%} of busy time joined the "
                  f"compiled program", file=sys.stderr)
        return None
    total = 0.0
    for name, s in secs.items():
        if name is not None:
            sig, ch, _ = table[name]
            if scope in ch or (extra is not None and extra(ch, sig)):
                total += s
    return 1e3 * total / run.records["steps"]
