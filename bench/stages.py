"""Device time of each ``fz.stage.*`` scope of the program, per call.

The program opens one innermost ``fz.stage.<name>`` scope around every
operation of its compress and decompress programs (``repro.core.fz``); the
compiler keeps the scope in the op metadata of what it compiles. A profile
names each device op by its HLO instruction, so ``stage_map`` maps the
instructions of a compiled program's text to stages and ``split`` joins a
traced window's op times (``xplane.Reduced.op_s``) to that map. An op whose
instruction is not in the map comes from another program: the join then
fails and no stage is reported.

Instructions the compiler makes itself (the steps of a cumsum, layout
copies, loops it builds) carry no scope. Each takes the stage of what it is
made for: the stage inside the computation it calls, else that of the
nearest instruction that uses its result, else of the nearest one it reads,
else that of the loop or call that runs it. What is left is ``other``.

The map is made after the window from the programs the eager wrapper
dispatched (``fz.lowered``), compiled again: the compile cache holds them.
The readers find the cell on the command line of ``bench/run.py``. A
program without ``fz.lowered`` or without stage scopes reports no stage.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import pathlib
import re
import sys

from .xplane import op_label

STAGES = {"compress": ("resolve_eb", "quantize", "collect_outliers",
                       "shuffle_encode", "compact_blocks"),
          "decompress": ("decode_blocks", "unshuffle", "dequantize")}
OTHER = "other"
PREFIX = "fz.stage."

_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


@dataclasses.dataclass
class Instr:
    label: str                  # xplane.op_label of its line
    comp: str                   # computation that holds it
    operands: list[str]
    calls: list[str]            # computations it calls
    stage: str | None           # innermost fz.stage.* of its metadata


def stage_of(op_name: str) -> str | None:
    """Innermost ``fz.stage.<name>`` of an op_name path, without the prefix."""
    found = [p for p in op_name.split("/") if p.startswith(PREFIX)]
    return found[-1][len(PREFIX):] if found else None


def _opcode_operands(rest: str) -> tuple[str, str]:
    """The opcode and operand list of an instruction's text after ``=``:
    skip the result shape (a tuple in parentheses, or one token)."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
    else:
        i = rest.find(" ")
    start = rest.find("(", i + 1)
    if start < 0:
        return rest[i + 1:].strip(), ""
    opcode = rest[i + 1:start].strip()
    depth = 0
    for j in range(start, len(rest)):
        depth += rest[j] == "("
        depth -= rest[j] == ")"
        if depth == 0:
            return opcode, rest[start + 1:j]
    return opcode, rest[start + 1:]


def parse(text: str) -> dict[str, Instr]:
    """Every instruction of an HLO module's text, by name, in text order."""
    out: dict[str, Instr] = {}
    comp = ""
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        calls = _CALLS.findall(rest)
        for group in _BRANCHES.findall(rest):
            calls += _OPERAND.findall(group)
        op = _OP_NAME.search(rest)
        opcode, operands = _opcode_operands(rest)
        # the compiler shares one constant among all its uses, whatever
        # their stage, so a constant's metadata names none of them
        stage = stage_of(op.group(1)) if op and opcode != "constant" else None
        out[name] = Instr(label=op_label(f"%{name} = {rest}"), comp=comp,
                          operands=_OPERAND.findall(operands), calls=calls,
                          stage=stage)
    return out


def stage_map(text: str) -> dict[str, str]:
    """op label -> stage (or ``other``) for every instruction of a compiled
    HLO module's text."""
    instrs = parse(text)
    members = collections.defaultdict(list)
    users = collections.defaultdict(list)
    caller = {}
    for name, ins in instrs.items():
        members[ins.comp].append(name)
        for o in ins.operands:
            users[o].append(name)
        for c in ins.calls:
            caller.setdefault(c, name)

    @functools.cache
    def inside(comp: str) -> str | None:
        """The stage nearest the root of ``comp`` and what it calls."""
        for name in reversed(members[comp]):
            found = instrs[name].stage or next(
                filter(None, map(inside, instrs[name].calls)), None)
            if found:
                return found
        return None

    def own(name: str) -> str | None:
        ins = instrs[name]
        return ins.stage or next(filter(None, map(inside, ins.calls)), None)

    def nearest(name: str, edges) -> str | None:
        seen, queue = {name}, collections.deque(edges(name))
        while queue:
            n = queue.popleft()
            if n in seen or n not in instrs:
                continue
            seen.add(n)
            found = own(n)
            if found:
                return found
            queue.extend(edges(n))
        return None

    @functools.cache
    def resolve(name: str) -> str | None:
        found = (own(name) or nearest(name, lambda n: users[n])
                 or nearest(name, lambda n: instrs[n].operands))
        if found is None and instrs[name].comp in caller:
            found = resolve(caller[instrs[name].comp])
        return found

    return {ins.label: resolve(name) or OTHER for name, ins in instrs.items()}


def split(op_s: dict[str, float], smap: dict[str, str]):
    """``(stage -> seconds, labels missing from the map)`` of one
    direction's op times; the first is None when any op is missing."""
    missing = sorted(label for label in op_s if label not in smap)
    if missing:
        return None, missing
    out = dict.fromkeys(STAGES["compress"] + STAGES["decompress"] + (OTHER,), 0.0)
    for label, secs in op_s.items():
        out[smap[label]] += secs
    return out, []


def workload(argv=None) -> str | None:
    """The cell ``bench/run.py`` was started for (``--workload``)."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    return ap.parse_known_args(sys.argv[1:] if argv is None else argv)[0].workload


@functools.cache
def texts(root: str, name: str) -> dict[str, str] | None:
    """direction -> compiled text of the program the cell's window
    dispatched, or None where the program has no ``fz.lowered`` or no
    stage scopes."""
    import jax
    import jax.numpy as jnp
    from repro.core import fz
    from . import harness
    lowered = getattr(fz, "lowered", None)
    if lowered is None:
        return None
    cell = harness.load_cell(name, pathlib.Path(root))
    cfg = harness.fz_config(cell.traffic)
    # the window's fields are uncommitted arrays, which lower as bare shapes
    x = jax.ShapeDtypeStruct(tuple(cell.config["shape"]), jnp.float32)
    c = jax.eval_shape(lambda d: fz.compress(d, cfg), x)
    out = {}
    for d, arg in (("compress", x), ("decompress", c)):
        low = lowered(d, arg, cfg)
        text = low.compile().as_text()
        if PREFIX not in text:
            # The persistent cache keys leave op metadata out, so a program
            # built without the scopes may have been loaded in its place.
            enabled = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            try:
                text = low.compile().as_text()
            finally:
                jax.config.update("jax_enable_compilation_cache", enabled)
        if PREFIX not in text:
            return None
        out[d] = text
    return out


@functools.cache
def maps(root: str, name: str) -> dict[str, dict[str, str]] | None:
    """direction -> stage map of the cell's dispatched programs."""
    found = texts(root, name)
    return found and {d: stage_map(text) for d, text in found.items()}


def stage_ms(ctx, d: str, stage: str) -> float | None:
    """Device ms per ``d`` call under ``stage`` in the traced window."""
    t, name = ctx.trace, workload()
    if t is None or not t.calls[d] or not t.op_s[d] or name is None:
        return None
    root = pathlib.Path(__file__).resolve().parents[1]
    smaps = maps(str(root), name)
    if smaps is None:
        return None
    secs, missing = split(t.op_s[d], smaps[d])
    if secs is None:
        print(f"stage_ms.{d}: ops not in the dispatched program: {missing[:5]}",
              file=sys.stderr, flush=True)
        return None
    return 1e3 * secs[stage] / t.calls[d]
