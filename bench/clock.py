"""One clock for the host and the device, and what the host did in each gap.

The device's clock is not the host's, but the host's runtime events bound
the offset between them (host time = device time + offset). The k-th program
execution on a chip (its ``XLA Modules`` event) runs after the k-th launch
(``TpuLoadedExecutable::ExecuteLaunch``) has started, and ends before the
host handles the k-th completion (``tpu::System::Execute=>Done``). So

    max(launch start - execution start) <= offset
        <= min(completion start - execution end)

``offset`` returns that interval; its width is the clock skew the
measurement cannot remove. The lower bound is the offset used.

``gap_phases`` shifts each idle gap between two calls' device work onto the
host's clock and splits it at the host's own marks into four phases that sum
to the gap:

- ``completion``: the previous program's last op ends, up to the end of its
  ``bench.<dir>`` span (``block_until_ready`` returns);
- ``caller``: from there to the start of the next ``fz.<dir>`` span (the
  harness's loop);
- ``wrapper``: ``fz.<dir>`` start to its ``PjitFunction`` start (config
  resolution, dispatch accounting);
- ``dispatch``: ``PjitFunction`` start to the next program's first op.

``completion`` is charged to the call that just ended, the others to the
call about to start: the direction whose ``bench.*`` span holds each.

    PYTHONPATH=src python3 -m bench.clock --workload <cell> --seed <n> --seconds <s>

runs the cell's traced window through the harness on the TPU it is started
on, and prints one JSON line: the offset interval, the skew, and per
direction the phases and the between-call idle (the part of each gap inside
the direction's ``bench.*`` span, as ``idle_share`` charges it) in ms per
call.
"""
from __future__ import annotations

import bisect

from .xplane import DIRECTIONS, SPAN_PREFIX, Event, union

LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
DONE = "tpu::System::Execute=>Done"
PJIT = "PjitFunction("
PHASES = ("completion", "caller", "wrapper", "dispatch")


def _named(host: list[Event], name: str) -> list[Event]:
    return sorted((e for e in host if e.name == name), key=lambda e: e.start)


def offset(modules: list[Event], host: list[Event]) -> tuple[float, float] | None:
    """``(low, high)`` bounds of host minus device time, in seconds, from one
    chip's executions; None where executions, launches and completions do
    not pair up one to one, or the bounds cross."""
    modules = sorted(modules, key=lambda m: m.start)
    launches, dones = _named(host, LAUNCH), _named(host, DONE)
    if not modules or not len(modules) == len(launches) == len(dones):
        return None
    low = max(la.start - m.start for m, la in zip(modules, launches))
    high = min(dn.start - m.end for m, dn in zip(modules, dones))
    return (low, high) if low <= high else None


def gap_phases(modules: list[Event], ops: list[Event], host: list[Event],
               shift: float) -> dict[str, dict[str, float]] | None:
    """direction -> phase -> seconds summed over the window's between-call
    gaps, with ``between`` the part of the gaps inside the direction's
    spans; None where the executions do not pair with the ``bench.*`` spans
    one to one."""
    spans = sorted((e for e in host if e.name in {SPAN_PREFIX + d for d in DIRECTIONS}),
                   key=lambda e: e.start)
    modules = sorted(modules, key=lambda m: m.start)
    if len(spans) != len(modules) or not spans:
        return None
    wrappers = _named(host, "fz.compress") + _named(host, "fz.decompress")
    pjits = sorted((e for e in host if e.name.startswith(PJIT)), key=lambda e: e.start)
    out = {d: dict.fromkeys(PHASES + ("between",), 0.0) for d in DIRECTIONS}
    starts = [m.start for m in modules]
    first = {}   # execution -> its first op's start; last: its last op's end
    last = {}
    for s, e in union((o.start, o.end) for o in ops):
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < modules[k].end:
            first.setdefault(k, s)
            last[k] = e
    for k in range(len(modules) - 1):
        if k not in last or k + 1 not in first:
            continue
        prev, nxt = spans[k], spans[k + 1]
        end, start = last[k] + shift, first[k + 1] + shift
        fz = next((w for w in wrappers if nxt.start <= w.start <= nxt.end), None)
        pj = fz and next((p for p in pjits if fz.start <= p.start <= fz.end), None)
        if pj is None:
            return None
        marks = (end, prev.end, fz.start, pj.start, start)
        d_prev, d_next = prev.name[len(SPAN_PREFIX):], nxt.name[len(SPAN_PREFIX):]
        for phase, a, b in zip(PHASES, marks, marks[1:]):
            out[d_prev if phase == "completion" else d_next][phase] += b - a
        out[d_prev]["between"] += max(0.0, min(prev.end, start) - end)
        out[d_next]["between"] += max(0.0, start - max(nxt.start, end))
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from . import harness, stages, xplane
    read = xplane.read
    kept = []

    def keep(path):   # harness.run deletes the trace once it has read it
        kept.append(read(path))
        return kept[-1]
    xplane.read = keep
    try:
        cell = harness.load_cell(args.workload, harness.ROOT)
        result = harness.run(cell, args.seed, args.seconds, True, t_start)
    finally:
        xplane.read = read
    (devices, host), = kept
    (modules, ops), = devices.values()
    bounds = offset(modules, host)
    phases = bounds and gap_phases(modules, ops, host, bounds[0])
    reduced = xplane.reduce(devices, host)
    calls = reduced.calls
    # device ms per call of all ops, and of the ops whose own metadata
    # names no stage (their stage is inferred)
    found = stages.texts(str(harness.ROOT), args.workload)
    scoped = found and {d: {i.label for i in stages.parse(t).values() if i.stage}
                        for d, t in found.items()}
    out = {"workload": args.workload, "correct": result["correct"],
           "offset_ms": bounds and [1e3 * b for b in bounds],
           "clock_skew_ms": bounds and 1e3 * (bounds[1] - bounds[0]),
           "calls": calls,
           "op_ms": {d: 1e3 * sum(reduced.op_s[d].values()) / calls[d]
                     for d in DIRECTIONS},
           "inferred_ms": scoped and {
               d: 1e3 * sum(s for k, s in reduced.op_s[d].items()
                             if k not in scoped[d]) / calls[d] for d in DIRECTIONS},
           "host_gap_ms": phases and {d: {p: 1e3 * s / calls[d] for p, s in v.items()}
                                      for d, v in phases.items()},
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           "device": result["device"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
