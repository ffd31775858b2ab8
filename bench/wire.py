"""What a compiled training step puts across pods, and what it may.

``cross_pod`` reads a compiled program's text: every collective whose
replica groups hold devices of more than one pod, with its bytes per device
as ``hlo_cost`` counts them in the program (an all-gather moves its output,
an all-reduce twice its output, as a ring does; a collective inside a loop
counts once per trip; the clones the compiler makes of an async collective
count once; a 16-bit all-reduce that the CPU compiler widened to 32 bits
counts at 16).

``model`` is what the compressed exchange may put across pods, leaf by
leaf from the parameters' shapes and the exchange's settings: a leaf that
is floating and holds at least ``min_leaf_size`` elements crosses as the
program's FZ container (its shapes from ``fz.compress`` of a flat float32
leaf), all-gathered; smaller leaves are reduced exactly. A container piece
under ``MIN_GATHER_BYTES`` may be turned into an all-reduce by the compiler.

``check`` compares the two and returns the numbers the cell's ``correct``
holds at 0: all-gather bytes outside the containers' range, container
shapes not all-gathered or other shapes all-gathered, other kinds of
collective across pods, and all-reduce bytes past what the exact leaves,
the small pieces and the loss allow (so no full-precision gradient crosses).
A copy of ``chip_smoke.py``'s step check, kept with the benchmark.
"""
from __future__ import annotations

import collections
import math
import re

import numpy as np

# the v5e compiler turns an all-gather this small into an all-reduce (seen
# for the 8-byte eb_abs and the 128-byte bitflags of 4096-element leaves)
MIN_GATHER_BYTES = 1024
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
         "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"\b(\w+)\[([\d,]*)\]")
_OPERAND = re.compile(r"%([\w.\-]+)")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_COND = re.compile(r"\bcondition=%?([\w.\-]+)")
_CALLS = re.compile(r"\b(?:calls|to_apply|true_computation|false_computation)=%?([\w.\-]+)")
_CHANNEL = re.compile(r"channel_id=(\d+)")
_LAYOUT = re.compile(r"\{[^}]*\}")
_RG_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_RG_LIST = re.compile(r"replica_groups=\{(\{[\d,]+\}(?:,\{[\d,]+\})*)\}")


def shapes(text: str) -> list[tuple[str, int]]:
    """(HLO dtype, element count) of each array shape written in ``text``."""
    return [(dt, math.prod(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text) if dt in BYTES]


def groups(rest: str) -> np.ndarray | None:
    """The replica groups of a collective, one row per group."""
    m = _RG_IOTA.search(rest)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(math.prod(dims)).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        return ids.reshape(g, s)
    m = _RG_LIST.search(rest)
    if m:
        rows = [[int(d) for d in grp.split(",")] for grp in re.findall(r"\{([\d,]+)\}", m.group(1))]
        if len({len(r) for r in rows}) == 1:
            return np.asarray(rows)
    return None


def crosses_pod(rest: str, per_pod: int) -> bool:
    """True when some replica group holds devices of two pods (device ids
    of one pod are consecutive, ``per_pod`` of them)."""
    g = groups(rest)
    if g is None:
        return False
    pods = g // per_pod
    return bool((pods != pods[:, :1]).any())


def _parse(text: str):
    """``({computation: [(name, out shape, opcode, rest)]}, entry name)``."""
    comps: dict[str, list] = {}
    cur, entry = None, None
    for line in text.splitlines():
        if line and not line.startswith(" "):
            h = _HEADER.match(line.strip())
            cur = comps.setdefault(h.group(1), []) if h else None
            if h and line.startswith("ENTRY"):
                entry = h.group(1)
            continue
        m = _OP.match(line) if cur is not None else None
        if m:
            cur.append(m.groups())
    return comps, entry


def _trips(cond: list) -> int:
    """A scan's trip count: the constant its loop counter is compared with."""
    consts = {}
    for name, _, opcode, rest in cond:
        m = re.match(r"(-?\d+)\)", rest)
        if opcode == "constant" and m:
            consts[name] = int(m.group(1))
    for _, _, opcode, rest in cond:
        if opcode == "compare" and "direction=LT" in rest:
            for n in _OPERAND.findall(rest):
                if consts.get(n, 0) > 0:
                    return consts[n]
    pos = [v for v in consts.values() if v > 0]
    return max(pos) if pos else 1


def _multiplicity(comps: dict, entry: str | None) -> dict[str, float]:
    """Executions of each computation per run of the entry."""
    edges = collections.defaultdict(list)
    for cname, ops in comps.items():
        for _, _, opcode, rest in ops:
            body, cond = _BODY.search(rest), _COND.search(rest)
            if opcode == "while" and body:
                trips = _trips(comps.get(cond.group(1), [])) if cond else 1
                edges[cname].append((body.group(1), float(trips)))
            for callee in _CALLS.findall(rest):
                edges[cname].append((callee, 1.0))
    order, seen = [], set()

    def visit(n):
        if n in seen:
            return
        seen.add(n)
        for c, _ in edges.get(n, []):
            visit(c)
        order.append(n)
    mult = collections.defaultdict(float)
    if entry is not None:
        visit(entry)
        mult[entry] = 1.0
    for n in reversed(order):
        for c, w in edges.get(n, []):
            mult[c] += mult[n] * w
    return mult


def _widened(ops: list, name: str) -> bool:
    """An all-reduce the CPU compiler widened from 16 bits: its value (or a
    tuple element of it) goes straight back to a 16-bit type."""
    near = {name} | {n for n, _, opcode, rest in ops
                     if opcode == "get-tuple-element" and name in _OPERAND.findall(rest)}
    return any(shape.lstrip("(").startswith(("bf16", "f16", "u16", "s16"))
               and opcode in ("convert", "fusion", "copy")
               and near & set(_OPERAND.findall(rest.split(")")[0]))
               for _, shape, opcode, rest in ops)


def cross_pod(text: str, per_pod: int):
    """``(bytes per device by collective kind, set of (kind, dtype, elements)
    of each array a cross-pod collective outputs)``."""
    comps, entry = _parse(text)
    mult = _multiplicity(comps, entry)
    moved = collections.defaultdict(float)
    outs, clones = set(), set()
    for cname, ops in comps.items():
        for name, shape, opcode, rest in ops:
            base = opcode.removesuffix("-start")
            if base not in COLLECTIVES or not crosses_pod(rest, per_pod):
                continue
            ch = _CHANNEL.search(rest)
            if ch and "chain_id=" in rest:
                k = (ch.group(1), _LAYOUT.sub("", shape))
                if k in clones:
                    continue
                clones.add(k)
            arrays = shapes(shape)
            if opcode == "all-gather-start":        # (operands, outputs)
                arrays = arrays[len(arrays) // 2:]
            nbytes = sum(n * BYTES[dt] for dt, n in arrays)
            if base == "all-reduce":
                nbytes *= 2
                if _widened(ops, name):
                    nbytes //= 2
            moved[base] += mult.get(cname, 0.0) * nbytes
            outs |= {(base, dt, n) for dt, n in arrays}
    return dict(moved), outs


def _hlo_dtype(dtype) -> str:
    d = np.dtype(dtype)
    return f"{'s' if d.kind == 'i' else d.kind}{8 * d.itemsize}"


def model(leaf_shapes: list[tuple[tuple[int, ...], object]], fz_config,
          min_leaf_size: int, n_pods: int) -> dict:
    """What the compressed exchange may move across pods, from each
    leaf's (shape, dtype) and the exchange's FZ settings."""
    import jax
    import jax.numpy as jnp
    from repro.core import fz
    gathers, large = set(), set()
    large_bytes = small_bytes = 0
    exact_bytes = 4                                     # the loss, f32
    for shape, dtype in leaf_shapes:
        n = math.prod(shape)
        if not (jnp.issubdtype(dtype, jnp.floating) and n >= min_leaf_size):
            exact_bytes += 4 * n
            continue
        c = jax.eval_shape(lambda x: fz.compress(x, fz_config),
                           jax.ShapeDtypeStruct((n,), jnp.float32))
        for a in jax.tree.leaves(c):
            nbytes = n_pods * a.size * a.dtype.itemsize
            k = (_hlo_dtype(a.dtype), n_pods * a.size)
            gathers.add(k)
            if nbytes >= MIN_GATHER_BYTES:
                large.add(k)
                large_bytes += nbytes
            else:
                small_bytes += nbytes
    return {"gathers": gathers, "large": large,
            "gather_bytes": (large_bytes, large_bytes + small_bytes),
            "all_reduce_bytes": 2 * (exact_bytes + small_bytes)}


LIMITS = {"wire_gather_bytes_off": 0, "wire_shapes_off": 0,
          "wire_other_collectives": 0, "wire_all_reduce_excess": 0}


def check(text: str, per_pod: int, want: dict) -> dict:
    """The wire numbers of a compiled step against ``model``'s ``want``."""
    moved, outs = cross_pod(text, per_pod)
    gathered = {(dt, n) for kind, dt, n in outs if kind == "all-gather"}
    lo, hi = want["gather_bytes"]
    g = moved.get("all-gather", 0.0)
    return {"wire_gather_bytes_off": max(lo - g, g - hi, 0.0),
            "wire_shapes_off": len(want["large"] - gathered) + len(gathered - want["gathers"]),
            "wire_other_collectives": len({k for k, _, _ in outs} - {"all-gather", "all-reduce"}),
            "wire_all_reduce_excess": max(moved.get("all-reduce", 0.0)
                                          - want["all_reduce_bytes"], 0.0)}
