"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the metrics read.

``read`` turns the file into plain tuples: for each chip (each
``/device:TPU:<n>`` plane) its program executions (the ``XLA Modules``
line) and its operations (``XLA Ops``), and the host events (every line of
``/host:CPU``). Times are seconds. ``reduce`` needs nothing but those
tuples, so it is tested on hand-made ones as well as on a recorded trace.

The device's clock is not the host's: in a recorded trace a program ends on
the device about 2 ms before the host sees it end. So nothing here compares
a device time with a host time. The harness marks each timed call with a
host span, ``bench.compress`` or ``bench.decompress``; the k-th program
execution on a chip belongs to the k-th span (each call runs one program),
or, where the counts differ, to the direction its module is named after.
An operation belongs to the execution that holds it. Then a direction's
busy time is the union of its operations' intervals, averaged over the
chips, and its idle share is one minus busy over the summed length of its
spans. Idle gaps are the holes between operations, named by the
executions on either side.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
DIRECTIONS = ("compress", "decompress")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float


def read(path: str):
    """``({chip plane: (modules, ops)}, host events)`` of one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            devices[plane.name] = (lines.get(MODULES_LINE, []), lines.get(OPS_LINE, []))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return devices, host


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def find(log_dir: str) -> str:
    """The one xplane file a trace wrote under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, found {files}")
    return files[0]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint cover of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_label(name: str) -> str:
    """An op event's name is its HLO text, ``%fusion.3 = u16[8,64]{...}
    fusion(...)``; its label is the instruction and its first result shape,
    ``fusion.3 u16[8,64]``."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name
    shape = _SHAPE.search(rest)
    return f"{head.lstrip('%')} {shape.group(0)}" if shape else head.lstrip("%")


def kernel_base(name: str) -> str:
    """``lorenzo_quant.1 u16[4]`` or ``lorenzo_quant.1`` -> ``lorenzo_quant``:
    an HLO instruction's name without its number."""
    head, dot, tail = name.split(" ")[0].rpartition(".")
    return head if dot and tail.isdigit() else name.split(" ")[0]


@dataclasses.dataclass
class Reduced:
    """What a traced window holds, per direction ``compress``/``decompress``."""
    window_s: float                    # first span's start to last span's end
    span_s: dict[str, float]           # summed length of the direction's spans
    calls: dict[str, int]              # spans of the direction
    busy_s: dict[str, float]           # device busy in its executions, chip mean
    busy_total_s: float                # device busy in the window, chip mean
    op_s: dict[str, dict[str, float]]  # direction -> op label -> seconds
    op_n: dict[str, dict[str, int]]    # direction -> op label -> events
    gaps: list[tuple[str, float]]      # longest idle gaps, named


def direction_of(module: str) -> str | None:
    for d in sorted(DIRECTIONS, key=len, reverse=True):
        if d in module:
            return d
    return None


def reduce(devices, host: list[Event], n_gaps: int = 10) -> Reduced:
    spans = sorted((e.start, e.end, e.name[len(SPAN_PREFIX):]) for e in host
                   if e.name in {SPAN_PREFIX + d for d in DIRECTIONS})
    if not spans:
        raise RuntimeError("no bench.compress/bench.decompress span in the trace")
    n_chips = max(len(devices), 1)
    busy = dict.fromkeys(DIRECTIONS, 0.0)
    busy_total = 0.0
    op_s = {d: {} for d in DIRECTIONS}
    op_n = {d: {} for d in DIRECTIONS}
    holes: list[tuple[float, str]] = []
    for modules, ops in devices.values():
        modules = sorted(modules, key=lambda m: m.start)
        if len(modules) == len(spans):
            dirs = [d for _, _, d in spans]
        else:
            dirs = [direction_of(m.name) for m in modules]
        starts = [m.start for m in modules]
        per_dir: dict[str, list] = {d: [] for d in DIRECTIONS}
        timed = []
        for op in ops:
            i = bisect.bisect_right(starts, (op.start + op.end) / 2) - 1
            if i < 0 or op.start >= modules[i].end or dirs[i] is None:
                continue
            d = dirs[i]
            per_dir[d].append((op.start, op.end))
            timed.append((op.start, op.end))
            label = op_label(op.name)
            op_s[d][label] = op_s[d].get(label, 0.0) + (op.end - op.start)
            op_n[d][label] = op_n[d].get(label, 0) + 1
        for d, ivs in per_dir.items():
            busy[d] += sum(e - s for s, e in union(ivs)) / n_chips
        cover = union(timed)
        busy_total += sum(e - s for s, e in cover) / n_chips
        for (_, e0), (s1, _) in zip(cover, cover[1:]):
            i = bisect.bisect_right(starts, e0) - 1
            j = bisect.bisect_right(starts, s1) - 1
            if i == j:
                name = f"inside {dirs[i]}"
            else:
                name = f"host between calls: {dirs[i]} -> {dirs[j]}"
            holes.append((s1 - e0, name))
    holes.sort(key=lambda h: -h[0])
    return Reduced(window_s=spans[-1][1] - spans[0][0],
                   span_s={d: sum(e - s for s, e, x in spans if x == d) for d in DIRECTIONS},
                   calls={d: sum(x == d for _, _, x in spans) for d in DIRECTIONS},
                   busy_s=busy, busy_total_s=busy_total, op_s=op_s, op_n=op_n,
                   gaps=[(name, secs) for secs, name in holes[:n_gaps]])
