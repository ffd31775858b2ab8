"""The ``fz.stage.*`` scopes of the compress and decompress programs.

Every instruction of a compiled program whose metadata names the program
(an ``op_name`` under ``jit(_compress_jit)``/``jit(_decompress_jit)``)
carries an innermost stage scope of its direction. Instructions the compiler
makes itself carry no op_name of the program; a profile's reduction assigns
those (``bench/stages.py``).
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import fz

STAGES = {"compress": {"resolve_eb", "quantize", "collect_outliers",
                       "shuffle_encode", "compact_blocks"},
          "decompress": {"decode_blocks", "unshuffle", "dequantize"}}
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*([a-z]+\d*\[[\d,]*\])?"
                   r".*?\s([a-z][\w-]*)\(([^)]*)\)")


def _innermost(op_name: str) -> str | None:
    found = [p for p in op_name.split("/") if p.startswith("fz.stage.")]
    return found[-1][len("fz.stage."):] if found else None


@pytest.mark.parametrize("shape", [(4, 32, 128), (40, 360), (20000,)],
                         ids=["3d", "2d", "1d"])
@pytest.mark.parametrize("strict", [True, False], ids=["strict", "paper"])
def test_every_program_op_has_one_stage(shape, strict):
    cfg = fz.FZConfig(eb=1e-3, exact_outliers=strict, use_kernels=True,
                      kernel_mode="staged")
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    c = jax.eval_shape(lambda d: fz.compress(d, cfg), x)
    for op, arg in (("compress", x), ("decompress", c)):
        text = fz.lowered(op, arg, cfg).compile().as_text()
        names = [m.group(1) for m in OP_NAME.finditer(text)
                 if m.group(1).startswith(f"jit(_{op}_jit)")]
        assert names
        stages = {n: _innermost(n) for n in names}
        unscoped = sorted(n for n, s in stages.items() if s is None)
        assert not unscoped, unscoped[:5]
        assert set(stages.values()) <= STAGES[op]
        if op == "compress":
            assert ("collect_outliers" in stages.values()) == strict


def test_lowered_is_what_the_wrapper_dispatches():
    """``fz.lowered`` resolves the config as the eager wrapper does and
    lowers the same jitted inner: the same module as a jit of the inner."""
    cfg = fz.FZConfig(eb=1e-3, use_kernels=True, kernel_mode="auto")
    x = jnp.linspace(0.0, 1.0, 8192, dtype=jnp.float32).reshape(2, 4096)
    resolved = fz._resolved(cfg, "compress", x.size, "float32")
    assert resolved.kernel_mode != "auto"
    assert fz.lowered("compress", x, cfg).as_text() == \
        fz._compress_jit.lower(x, resolved).as_text()
    c = fz.compress(x, cfg)
    assert fz.lowered("decompress", c, cfg).as_text() == \
        fz._decompress_jit.lower(c, fz._resolved(cfg, "decompress", c.n,
                                                 c.dtype_name)).as_text()
    with pytest.raises(ValueError):
        fz.lowered("both", x, cfg)


def _elements(shape: str | None) -> int:
    dims = shape[shape.index("[") + 1:-1] if shape else ""
    return math.prod(int(d) for d in dims.split(",") if d)


def test_outliers_are_collected_without_a_whole_field_op():
    """The strict program at 512^3 collects outliers with no scatter and no
    reduce-window over n elements: ``jnp.nonzero``'s whole-field bincount
    (a scatter-add of every element, after an n-element cumsum) stays out.
    Its compaction loop's body is named ``outlier_chunk``, under the stage."""
    n = 512 ** 3
    cfg = fz.FZConfig(eb=1e-3, use_kernels=True, kernel_mode="staged")
    text = fz.lowered("compress", jax.ShapeDtypeStruct((512,) * 3, jnp.float32),
                      cfg).compile().as_text()
    shapes, ops, body = {}, [], 0
    for line in text.splitlines():
        instr, name = INSTR.match(line), OP_NAME.search(line)
        if not instr:
            continue
        shapes[instr.group(1)] = instr.group(2)
        if name and _innermost(name.group(1)) == "collect_outliers":
            body += "/outlier_chunk/" in name.group(1)
            if instr.group(3) in ("scatter", "reduce-window"):
                ops.append(instr)
    for op in ops:
        operands = re.findall(r"%([\w.\-]+)", op.group(4))
        sizes = [_elements(shapes.get(o)) for o in [op.group(1), *operands]]
        assert max(sizes) < n, op.group(0)[:200]
    assert ops and body
