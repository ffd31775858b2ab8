"""Trip-count-aware cost model over compiled (post-SPMD) HLO text.

XLA's built-in ``cost_analysis()`` visits every while body ONCE, so any
scan-over-layers / scan-over-chunks program under-reports FLOPs, bytes and
collectives by the trip counts. This module re-derives the three roofline
inputs from the HLO text itself:

  * parse every computation into (op, output shape, operands, attributes);
  * FLOPs: 2 * prod(output dims) * prod(contracted dims) per ``dot``
    (convolutions are not used by this framework's models);
  * bytes: DOT-ADJACENT traffic model — for every ``dot``, operand bytes +
    output bytes (each matmul reads its inputs from and writes its result to
    HBM once). Naive fusion-boundary models fail on scan programs: while
    bodies thread full stacked [L, ...] parameter arrays and loop-carry
    tuples through every iteration, so counting fusion outputs/operands
    overstates traffic by orders of magnitude. Matmuls dominate transformer
    traffic at these shapes; elementwise fusion flows are the same order as
    the dot outputs they consume (documented approximation);
  * collective bytes: ring-model cost per op — all-gather: output bytes
    (each device receives ~the full gathered array); all-reduce: 2x output
    (ring = reduce-scatter + all-gather); reduce-scatter: operand bytes
    (~full input transits each device); all-to-all / collective-permute:
    output bytes. Start/done pairs counted once, and so is an op that the
    TPU compiler clones into the start/update/done computations of an async
    collective fusion (same ``channel_id`` and shape whatever its layout's
    memory space, tagged ``chain_id``;
    XLA:CPU gives every shard_map collective channel 1, so the channel
    alone is no key);
  * call-graph multipliers: while bodies/conditions multiply by the trip
    count recovered from the condition's ``compare(counter, constant)``;
    fusion/call computations inherit the caller's multiplier.

Shapes in a partitioned module are per-device, so all totals are per-device.
Validated against analytic 6*N*D in tests/test_hlo_cost.py.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\s*\{\s*$")
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_SHAPE_TOKEN = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND = re.compile(r"%([\w.\-]+)")
_ATTR_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_ATTR_BODY = re.compile(r"body=%?([\w.\-]+)")
_ATTR_COND = re.compile(r"condition=%?([\w.\-]+)")
_CONSTANT = re.compile(r"constant\((-?\d+)\)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CHANNEL = re.compile(r"channel_id=(\d+)")
_LAYOUT = re.compile(r"\{[^}]*\}")


def _shape_dims(shape_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for m in _SHAPE_TOKEN.finditer(shape_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((dt, dims))
    return out


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _shape_dims(shape_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def compiled_memory_traffic(compiled) -> dict:
    """Buffer-assignment HBM-traffic proxy for a compiled executable.

    Model: arguments are read once, outputs written once, and every temp
    (XLA-materialized intermediate) is written once and read once — so
    ``traffic = args + outputs + 2 * temps``. ``traffic_ratio`` normalizes by
    the unavoidable ``args + outputs``; a perfectly fused program scores ~1.0,
    a program that round-trips an input-sized intermediate scores >= ~3.0.
    Used by tests/test_kernels.py to pin the fused-decompress data-movement
    claim and by benchmarks/bench_breakdown.py's measured-traffic column.

    Caveat (documented, load-bearing for how the fused-compress claim is
    pinned): under the Pallas *interpreter* a kernel becomes an XLA loop whose
    carried operands double-buffer the kernel's full outputs, so temp bytes
    overstate a megakernel's real HBM traffic by O(outputs). The compress-side
    pin therefore uses :func:`materialized_shapes` (no code-stream-sized
    buffer exists at all) instead of this byte model.
    """
    ma = compiled.memory_analysis()
    args = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    traffic = args + out + 2 * temp
    return {"argument_bytes": args, "output_bytes": out, "temp_bytes": temp,
            "traffic_bytes": traffic,
            "traffic_ratio": traffic / max(args + out, 1)}


def materialized_shapes(hlo_text: str, *, dtype: str = "u16",
                        min_elems: int = 0) -> set[tuple[int, ...]]:
    """Distinct ``dtype`` buffer shapes with >= ``min_elems`` elements in an
    optimized-HLO dump. ``min_elems = padded stream length`` makes this a
    direct mechanical check of the §3.5 fusion claim: a pipeline that
    round-trips the u16 code (or shuffled-word) stream through HBM must
    materialize a u16 buffer of at least that many elements somewhere."""
    out: set[tuple[int, ...]] = set()
    for m in re.finditer(rf"{re.escape(dtype)}\[([\d,]+)\]", hlo_text):
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        n = 1
        for d in dims:
            n *= d
        if n >= min_elems:
            out.add(dims)
    return out


@dataclasses.dataclass
class Op:
    name: str
    out_shape: str
    opcode: str
    rest: str          # everything after the opening paren (operands + attrs)


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[Op]
    shapes: dict       # op name -> output shape string


def parse_computations(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        h = _COMP_HEADER.match(line.strip()) if line and not line.startswith(" ") else None
        if h and "{" in line:
            cur = Computation(h.group(1), [], {})
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_LINE.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        cur.ops.append(Op(name, shape, opcode, rest))
        cur.shapes[name] = shape
    return comps


def _operand_names(rest: str) -> list[str]:
    """Operand refs before the attribute section (first ')' closes the args)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(rest[:i])
    return _OPERAND.findall(rest)


def _dot_flops(op: Op, comp: Computation) -> float:
    out_elems = 1
    for _, dims in _shape_dims(op.out_shape):
        for d in dims:
            out_elems *= d
    names = _operand_names(op.rest)
    if not names:
        return 0.0
    lhs_shape = comp.shapes.get(names[0], "")
    lhs_dims_list = _shape_dims(lhs_shape)
    if not lhs_dims_list:
        return 0.0
    lhs_dims = lhs_dims_list[0][1]
    mc = _CONTRACT.search(op.rest)
    k = 1
    if mc:
        for idx in mc.group(1).split(","):
            if idx and int(idx) < len(lhs_dims):
                k *= lhs_dims[int(idx)]
    return 2.0 * out_elems * k


def _is_promoted_16bit(comp: Computation, ar_name: str) -> bool:
    """True if the all-reduce ``ar_name`` is a 16-bit reduction promoted to
    f32 by XLA-CPU's AllReducePromotion pass (on TPU it would run at 16-bit).

    Signature: its value is converted straight back to a 16-bit type — either
    a direct consumer, or (tuple ARs) a consumer of a get-tuple-element of it.
    The pre-convert is often absorbed into the producing dot, so we look
    downstream, not upstream.
    """
    layer1 = {ar_name}
    # include get-tuple-element wrappers
    for op in comp.ops:
        if op.opcode == "get-tuple-element" and ar_name in _operand_names(op.rest):
            layer1.add(op.name)
    for op in comp.ops:
        if not op.out_shape.lstrip("(").startswith(("bf16", "f16", "u16", "s16")):
            continue
        if op.opcode in ("convert", "fusion", "copy") and \
                any(nm in layer1 for nm in _operand_names(op.rest)):
            return True
    return False


_RG_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_RG_LIST = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def crosses_pod(rest: str, devices_per_pod: int) -> bool:
    """True if this collective's replica groups span a pod boundary.

    Handles both the explicit ``{{0,256},...}`` and the iota
    ``[G,S]<=[dims]T(perm)`` forms (decode the iota, reshape to groups, and
    check whether any group mixes device-id // devices_per_pod)."""
    m = _RG_IOTA.search(rest)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        groups = ids.reshape(g, s)
        pods = groups // devices_per_pod
        return bool((pods != pods[:, :1]).any())
    m = _RG_LIST.search(rest)
    if m:
        ids = np.array([int(d) for d in m.group(1).split(",")])
        return bool((ids // devices_per_pod != ids[0] // devices_per_pod).any())
    return False


def _trip_count(cond: Computation) -> int:
    """Recover scan trip count from compare(counter, constant) in the cond."""
    consts: dict[str, int] = {}
    for op in cond.ops:
        mc = _CONSTANT.search(op.opcode + "(" + op.rest)
        if op.opcode == "constant":
            m2 = re.search(r"constant\((-?\d+)\)", "constant(" + op.rest)
            if m2:
                consts[op.name] = int(m2.group(1))
    for op in cond.ops:
        if op.opcode == "compare" and "direction=LT" in op.rest:
            for nm in _operand_names(op.rest):
                if nm in consts and consts[nm] > 0:
                    return consts[nm]
    # fallback: any positive s32 constant
    pos = [v for v in consts.values() if v > 0]
    return max(pos) if pos else 1


def analyze(text: str, devices_per_pod: int | None = None,
            tag_pattern: str | None = None) -> dict:
    """``devices_per_pod``: when set (multi-pod mesh), collectives whose
    replica groups span pods are accounted separately as cross-pod bytes
    (they ride DCN, not ICI — see hlo_analysis.roofline_terms).

    ``tag_pattern``: optional regex run over each cross-pod collective's
    ``op_name`` metadata (which carries the jax ``named_scope`` stack
    through compilation). Matching ops are additionally grouped under
    ``cross_pod_by_tag[tag][collective]`` — this is how per-bucket wire
    bytes of the bucketed compressed reduce are attributed and verified
    against the analytic container model
    (``dist.bucketed_reduce.expected_cross_pod_bytes``, tag pattern
    ``dist.bucketed_reduce.BUCKET_TAG_PATTERN``)."""
    comps = parse_computations(text)
    tag_re = re.compile(tag_pattern) if tag_pattern else None

    entry = None
    for name, c in comps.items():
        if re.match(r"main", name) or name.startswith("main"):
            entry = name
    if entry is None:  # ENTRY computation name fallback: the last one
        entry = list(comps)[-1] if comps else None
    if entry is None:
        return {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0}

    # which computations are fusion-internal (compute-only, no byte traffic)
    fusion_called: set[str] = set()
    for c in comps.values():
        for op in c.ops:
            if op.opcode == "fusion":
                m = _ATTR_CALLS.search(op.rest)
                if m:
                    fusion_called.add(m.group(1))

    # static weighted call edges: caller -> [(callee, weight)]
    edges: dict[str, list[tuple[str, float]]] = defaultdict(list)
    for cname, c in comps.items():
        for op in c.ops:
            if op.opcode == "while":
                mb = _ATTR_BODY.search(op.rest)
                mc = _ATTR_COND.search(op.rest)
                trips = 1
                if mc and mc.group(1) in comps:
                    trips = _trip_count(comps[mc.group(1)])
                if mb:
                    edges[cname].append((mb.group(1), float(trips)))
                if mc:
                    edges[cname].append((mc.group(1), float(trips + 1)))
            else:
                for attr in (_ATTR_CALLS, _ATTR_BODY, _ATTR_COND):
                    m2 = attr.search(op.rest)
                    if m2 and m2.group(1) in comps:
                        edges[cname].append((m2.group(1), 1.0))

    # topological accumulation (HLO call graphs are DAGs)
    order: list[str] = []
    state: dict[str, int] = {}

    def dfs(n: str):
        if state.get(n) == 2:
            return
        state[n] = 1
        for child, _ in edges.get(n, []):
            if state.get(child) != 1:
                dfs(child)
        state[n] = 2
        order.append(n)

    dfs(entry)
    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    for n in reversed(order):
        for child, w in edges.get(n, []):
            mult[child] += mult[n] * w

    flops = 0.0
    bytes_ = 0.0
    coll_bytes = 0.0
    cross_pod_bytes = 0.0
    coll_detail: dict[str, float] = defaultdict(float)
    cross_pod_by_tag: dict[str, dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    clones: set[tuple[str, str]] = set()
    for cname, c in comps.items():
        m_here = mult.get(cname, 0.0)
        if m_here == 0.0:
            continue
        for op in c.ops:
            if op.opcode == "dot":
                flops += m_here * _dot_flops(op, c)
                opnd = sum(_shape_bytes(c.shapes.get(nm, ""))
                           for nm in _operand_names(op.rest))
                bytes_ += m_here * (opnd + _shape_bytes(op.out_shape))
            base = op.opcode[:-6] if op.opcode.endswith("-start") else op.opcode
            if base in COLLECTIVES:
                ch = _CHANNEL.search(op.rest)
                if ch and "chain_id=" in op.rest:
                    # a clone's layout may name another memory space (S(1))
                    key = (ch.group(1), _LAYOUT.sub("", op.out_shape))
                    if key in clones:
                        continue
                    clones.add(key)
                if base == "all-reduce":        # ring: RS + AG
                    b = 2 * _shape_bytes(op.out_shape)
                elif base == "reduce-scatter":  # ~full input transits
                    b = sum(_shape_bytes(c.shapes.get(nm, ""))
                            for nm in _operand_names(op.rest))
                else:
                    b = _shape_bytes(op.out_shape)
                # XLA-CPU's AllReducePromotion rewrites 16-bit all-reduces to
                # convert->f32-all-reduce->convert; on TPU they stay 16-bit.
                # See through the promotion (detected via the convert-back
                # consumer) and cost the op at half width.
                if base in ("all-reduce", "reduce-scatter") and \
                        _is_promoted_16bit(c, op.name):
                    b //= 2
                if devices_per_pod and crosses_pod(op.rest, devices_per_pod):
                    cross_pod_bytes += m_here * b
                    coll_detail[base + "@pod"] += m_here * b
                    if tag_re is not None:
                        mo = _OP_NAME.search(op.rest)
                        mt = tag_re.search(mo.group(1)) if mo else None
                        if mt:
                            tag = mt.group(1) if mt.groups() else mt.group(0)
                            cross_pod_by_tag[tag][base] += m_here * b
                else:
                    coll_bytes += m_here * b
                    coll_detail[base] += m_here * b
    return {
        "flops": flops,
        "bytes": bytes_,
        "collective_bytes": coll_bytes,
        "cross_pod_bytes": cross_pod_bytes,
        "collective_detail": dict(coll_detail),
        "cross_pod_by_tag": {t: dict(d) for t, d in cross_pod_by_tag.items()},
        "n_computations": len(comps),
    }
