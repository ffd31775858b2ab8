"""Bytes that the rooflines divide by HBM bandwidth.

Two kinds:

- a direction's least traffic, which any implementation has to move: a
  compress reads the field twice (once for the range that a relative bound
  needs, once to quantize) and writes the used container bytes; a
  decompress reads the used container bytes and writes the field once;
- a Pallas kernel's traffic, from the operand and result shapes of its
  ``tpu_custom_call`` in the compiled program, found by the HLO name the
  kernel's wrapper gives it. An operand passed twice (the same HLO value)
  is read once. So the count follows a kernel whose inputs or outputs change.
"""
from __future__ import annotations

import re

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(([^)]*)\)")
_OPERAND_SHAPES = re.compile(r"operand_layout_constraints=\{(.*?)\}\s*,\s*\w+=")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in ``text`` (``f32[3,4]`` -> 48)."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += DTYPE_BYTES[dtype] * n
    return total


def _split_top(text: str) -> list[str]:
    """Split ``a{..}, b{..}`` on the commas outside any brackets."""
    parts, depth, cur = [], 0, ""
    for ch in text:
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == "," and depth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    return parts + [cur] if cur.strip() else parts


def kernel_bytes(hlo_text: str) -> dict[str, list[int]]:
    """``{kernel name: [bytes moved by each of its custom calls]}``."""
    from .xplane import kernel_base
    out: dict[str, list[int]] = {}
    for line in hlo_text.splitlines():
        if KERNEL_TARGET not in line:
            continue
        m = _CALL.match(line)
        shapes = _OPERAND_SHAPES.search(line)
        if not m or not shapes:
            raise ValueError(f"unreadable kernel call: {line[:200]}")
        name, result, operands = m.groups()
        names = [o.strip() for o in operands.split(",") if o.strip()]
        layouts = _split_top(shapes.group(1))
        if len(names) != len(layouts):
            raise ValueError(f"{name}: {len(names)} operands, {len(layouts)} shapes")
        distinct = dict(zip(names, layouts))
        moved = shape_bytes(result) + sum(shape_bytes(s) for s in distinct.values())
        out.setdefault(kernel_base(name), []).append(moved)
    return out


def least_bytes(direction: str, source_bytes: int, used_bytes: int) -> int:
    """The traffic any implementation of one call has to move."""
    if direction == "compress":
        return 2 * source_bytes + used_bytes
    if direction == "decompress":
        return used_bytes + source_bytes
    raise ValueError(f"unknown direction {direction!r}")
