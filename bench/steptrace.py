"""A traced window of training steps, reduced to what the step metrics read.

Each timed step runs inside one host span, ``bench.step``. Every device op
of the traced window belongs to a step: the trace starts after set-up and
stops when the last step's loss is on the host. An op's class comes from
the compiled step's text (``classes``):

- ``fz_compress`` / ``fz_decompress``: the op sits in one of the FZ
  program's ``fz.stage.*`` scopes (``bench.stages.STAGES``) or inside its
  jitted compress or decompress program (``_compress_jit``,
  ``_decompress_jit`` on its op's path): the compressor's own work inside
  the gradient hop;
- ``gather``: a collective whose replica groups span the pods: the
  containers' all-gathers (and the few pieces the compiler turned into
  all-reduces, and the loss's mean);
- ``other``: the rest: forward, backward, optimizer, in-pod collectives
  and the hop's mean and residual around the compressor.

An instruction whose op metadata carries a path of scopes takes its class
from it. One the compiler made (copies, loop plumbing, a cumsum rebuilt as
reduce-windows, with no metadata or a bare op name) takes the class of the
computation it calls, else of the nearest instruction that uses it, else of
the nearest one it reads, else of the loop or call that runs it, as
``bench.stages`` does for the FZ programs. A loop's or call's own device
event spans those of the ops it runs, so it is not counted.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import re

from . import stages, wire
from .xplane import Event, op_label, union

SPAN = "bench.step"
CLASSES = ("fz_compress", "fz_decompress", "gather", "other")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
# op metadata with a path of scopes comes from the traced program; the
# compiler names what it rebuilds (a cumsum's reduce-windows) without one,
# or with the path of the FZ program it sits in but no stage
_TRACED_META = re.compile(r'metadata=\{[^}]*?op_name="[^"]*/')
_IN_COMPRESS = re.compile(r'metadata=\{[^}]*?op_name="[^"]*jit\(_compress_jit\)')
_IN_DECOMPRESS = re.compile(r'metadata=\{[^}]*?op_name="[^"]*jit\(_decompress_jit\)')
# a loop's or call's device event spans the events of the ops it runs
CONTAINERS = ("while", "conditional", "call")
CONTAINER = "container"


def classes(text: str, per_pod: int) -> dict[str, str]:
    """op label -> class for every instruction of a compiled step's text."""
    compress, decompress = set(stages.STAGES["compress"]), set(stages.STAGES["decompress"])
    instrs = stages.parse(text)
    decided: dict[str, str | None] = {}
    opcodes: dict[str, str] = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(1) not in instrs:
            continue
        name, rest = m.groups()
        opcode, _ = stages._opcode_operands(rest)
        opcodes[name] = opcode
        ins = instrs[name]
        if opcode in CONTAINERS:
            decided[name] = CONTAINER
        elif opcode.removesuffix("-start") in wire.COLLECTIVES and wire.crosses_pod(rest, per_pod):
            decided[name] = "gather"
        elif ins.stage in compress or _IN_COMPRESS.search(rest):
            decided[name] = "fz_compress"
        elif ins.stage in decompress or _IN_DECOMPRESS.search(rest):
            decided[name] = "fz_decompress"
        elif _TRACED_META.search(rest) and not opcode.endswith("-done"):
            decided[name] = "other"
        else:
            decided[name] = None
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
        for name in reversed(members[comp]):
            found = own(name)
            if found:
                return found
        return None

    def own(name: str) -> str | None:
        ins = instrs[name]
        if opcodes.get(name, "").endswith("-done") and ins.operands:
            return decided.get(ins.operands[0])
        found = decided.get(name) or next(filter(None, map(inside, ins.calls)), None)
        return None if found == CONTAINER and decided.get(name) != CONTAINER else found

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

    return {ins.label: resolve(name) or "other" for name, ins in instrs.items()}


@dataclasses.dataclass
class StepTrace:
    """A traced window of steps."""
    steps: int                              # bench.step spans
    window_s: float                         # first span's start to last span's end
    span_s: float                           # summed length of the spans
    busy_s: dict[str, float]                # device -> union of its op intervals
    op_s: dict[str, float]                  # op label -> seconds, first device
    op_n: dict[str, int]                    # op label -> events, first device
    gaps: list[tuple[str, float]]           # longest idle gaps of the first device

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def _device_order(name: str):
    tail = name.rsplit(":", 1)[-1]
    return (0, int(tail)) if tail.isdigit() else (1, name)


def reduce(devices: dict, host: list[Event], n_gaps: int = 10) -> StepTrace:
    """``devices``/``host`` as ``xplane.read`` returns them."""
    spans = sorted((e.start, e.end) for e in host if e.name == SPAN)
    if not spans:
        raise RuntimeError("no bench.step span in the trace")
    busy, op_s, op_n, holes = {}, {}, {}, []
    for i, dev in enumerate(sorted(devices, key=_device_order)):
        modules, ops = devices[dev]
        cover = union((op.start, op.end) for op in ops)
        busy[dev] = sum(e - s for s, e in cover)
        if i:
            continue
        for op in ops:
            label = op_label(op.name)
            op_s[label] = op_s.get(label, 0.0) + (op.end - op.start)
            op_n[label] = op_n.get(label, 0) + 1
        starts = sorted(m.start for m in modules)
        for (_, e0), (s1, _) in zip(cover, cover[1:]):
            between = bisect.bisect_right(starts, s1) - bisect.bisect_right(starts, e0)
            holes.append((s1 - e0, "host between steps" if between else "inside a step"))
    holes.sort(key=lambda h: -h[0])
    return StepTrace(steps=len(spans), window_s=spans[-1][1] - spans[0][0],
                     span_s=sum(e - s for s, e in spans), busy_s=busy,
                     op_s=op_s, op_n=op_n,
                     gaps=[(name, secs) for secs, name in holes[:n_gaps]])


def split(trace: StepTrace, cls: dict[str, str]):
    """``(class -> device seconds, seconds of ops not in the step)``. A
    loop's or call's own event is left out: the ops it runs are counted."""
    out = dict.fromkeys(CLASSES, 0.0)
    missing = 0.0
    for label, secs in trace.op_s.items():
        c = cls.get(label)
        if c == CONTAINER:
            continue
        if c is None:
            missing += secs
            c = "other"
        out[c] += secs
    return out, missing
