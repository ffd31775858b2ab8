"""Fused pre-quantization + Lorenzo + code-conversion Pallas kernel (paper §3.2).

One pass: float data -> saturating sign-magnitude u16 codes, branch-free
(the paper's "pred-quant-v2": no radius shift, no outlier path, fewer
branches -> no warp divergence; on TPU this becomes select-only VPU code).

Halo handling (TPU adaptation): cuSZ's CUDA kernel re-quantizes chunk-border
elements redundantly per thread block. Here each grid step owns a band of
leading-axis rows/planes and receives a 1-row halo *view of the same input
array* via a second BlockSpec (block shape 1 along the banded axis makes the
index map element-granular), so no shifted copies are materialized in HBM —
traffic is n + n/band vs. the GPU version's redundant boundary recompute.

Banding: the band covers all trailing axes, so all trailing-axis differences
are band-internal; only the leading-axis difference needs the halo. The
first band masks its (clamped) halo to zero via pl.program_id.

Strict mode (beyond the paper): with ``with_residual=True`` the kernel also
writes the int32 residual ``delta - decode(code)``, nonzero exactly where a
code saturated; ``kernels/ops.py`` compacts it into the exact-outlier side
channel with the reference's own ``core.quant.collect_outliers``: one read
of the residual to count each row's outliers, then a loop that gathers only
the rows holding outliers (no trip when there are none).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernelspec import (SMEM, BlockDecl, KernelSpec,
                                       register_spec)
from repro.core import quant as _quant

MAX_MAG = 0x7FFF
MAX_BAND = 8                  # leading-axis rows/planes per grid step
VMEM_BAND_BUDGET = 4 << 20    # bytes of band input in VMEM (headroom cap)
VMEM_LIMIT = 64 << 20         # scoped VMEM per kernel (v5e has 128 MiB)
ROW_1D = 1024                 # row width of the flattened-1D kernel view
# leading rows per grid step of the 2D and flattened-1D views: a multiple of
# every halo tile, so the previous band's last row is always the last row of
# one halo block, and of the u16 output's (16, 128) tile
ROWS_BAND = 2 * MAX_BAND


def band_for(trailing_elems: int, *, itemsize: int = 4) -> int:
    """Rows/planes per band so the band's *input* stays within the VMEM
    budget (large 3D fields: a single 1024x1024 f32 plane is 4 MiB).

    Dtype-aware: the budget divides by the input's real ``itemsize``, so a
    bf16 input (2 B/elem — kept native in HBM/VMEM, cast to f32 only inside
    the kernel body) gets twice the band an f32 input does instead of
    half-utilized bands. The resource analyzer (repro.analysis.resources)
    cross-checks this helper against its own footprint model.
    """
    return max(1, min(MAX_BAND,
                      VMEM_BAND_BUDGET // max(trailing_elems * itemsize, 1)))


def halo_rows(itemsize: int) -> int:
    """Rows in the 2D/1D halo block: one sublane tile of the input dtype
    (8 rows of f32, 16 of a packed 16-bit float). Mosaic only accepts blocks
    whose last two dims are (8, 128)-divisible or whole, so the halo row
    arrives inside a full tile and the kernel reads its last row."""
    return 8 * (4 // itemsize)


def _prequant(x: jax.Array, step) -> jax.Array:
    # the reference's own core.quant.quantize_scaled. The f32 cast makes
    # sub-f32 inputs quantize exactly as the reference's pre-cast data:
    # widening is exact.
    return _quant.quantize_scaled(x.astype(jnp.float32), *step)


def _to_code(d: jax.Array, code_mode: str) -> jax.Array:
    """int32 delta -> saturated code, still int32 (narrowed at the store)."""
    if code_mode == "sign_mag":
        return jnp.minimum(jnp.abs(d), MAX_MAG) | ((d >> 31) & 0x8000)
    # zigzag
    return jnp.minimum((d << 1) ^ (d >> 31), 0xFFFF)


def _from_code(c: jax.Array, code_mode: str) -> jax.Array:
    """int32 code -> the delta it stands for (``core.quant.from_codes``)."""
    if code_mode == "sign_mag":
        mag = c & MAX_MAG
        return jnp.where((c & 0x8000) != 0, -mag, mag)
    return (c >> 1) ^ -(c & 1)


def _prev(v: jax.Array, axis: int, first) -> jax.Array:
    """``v`` shifted by one along ``axis``; index 0 takes ``first``
    (broadcastable). A rotate plus an iota select lowers on Mosaic for any
    width, where a slice-and-concatenate at an unaligned offset does not."""
    rolled = pltpu.roll(v, 1, axis)
    at0 = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis) == 0
    return jnp.where(at0, first, rolled)


def band_codes(x_band: jax.Array, halo: jax.Array, step, *,
               ndim: int, code_mode: str, keep_halo) -> jax.Array:
    """Kernel-body helper: one band of input + its halo row -> int32 codes
    (:func:`band_delta` saturated by ``code_mode``)."""
    return _to_code(band_delta(x_band, halo, step, ndim=ndim,
                               keep_halo=keep_halo), code_mode)


def band_delta(x_band: jax.Array, halo: jax.Array, step, *,
               ndim: int, keep_halo) -> jax.Array:
    """One band of input + its halo row -> int32 Lorenzo deltas.

    ``step`` holds the three scalars of ``core.quant.step_scalars``.

    ``halo`` is the row (2D/1D views) or plane (3D) just before the band,
    shaped ``(1, *trailing)``. ``keep_halo`` is an int32 0/1 scalar: 0 on
    the first band, which gets the zero boundary condition instead (a
    multiply, not a broadcast boolean, which Mosaic cannot relayout).
    """
    q = _prequant(x_band, step)
    h = _prequant(halo, step) * keep_halo
    if ndim == 1:
        # flattened-1D layout (rows, C): continuous diff across row ends.
        # The previous element of col 0 is the last col of the previous
        # row; for the band's first row it is the halo row's last element.
        wrap = pltpu.roll(q, 1, 1)                 # [r, 0] = q[r, C-1]
        col0 = _prev(wrap, 0, pltpu.roll(h, 1, 1))
        at_col0 = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) == 0
        d = q - jnp.where(at_col0, col0, wrap)
    else:
        if ndim == 3:
            # leading axis is untiled: a plane concatenation is free
            prev = jnp.concatenate([h, q[:-1]], axis=0)
        else:
            prev = _prev(q, 0, h)
        d = q - prev
        for ax in range(1, ndim):
            d = d - _prev(d, ax, 0)
    return d


def _make_kernel(ndim: int, code_mode: str, halo_row: int,
                 with_residual: bool = False):
    def kernel(step_ref, x_ref, halo_ref, out_ref, *resid_ref):
        keep = jnp.minimum(pl.program_id(0), 1)
        halo = halo_ref[pl.ds(halo_row, 1)]
        d = band_delta(x_ref[...], halo,
                       (step_ref[0], step_ref[1], step_ref[2]),
                       ndim=ndim, keep_halo=keep)
        code = _to_code(d, code_mode)
        out_ref[...] = code.astype(jnp.uint16)
        if with_residual:
            resid_ref[0][...] = d - _from_code(code, code_mode)
    return kernel


def _geometry(shape: tuple[int, ...], itemsize: int):
    """(kernel view, band, halo block rows, halo row) for a 1-3D shape."""
    if len(shape) == 1:
        n = shape[0]
        view = (-(-n // ROW_1D), ROW_1D)
    else:
        view = tuple(shape)
    trailing_elems = 1
    for s in view[1:]:
        trailing_elems *= s
    if len(view) == 3:
        # a plane is its own halo: the leading block dim is untiled
        band = band_for(trailing_elems, itemsize=itemsize)
        return view, band, 1, 0
    hr = halo_rows(itemsize)
    return view, ROWS_BAND, hr, hr - 1


@functools.partial(jax.jit,
                   static_argnames=("code_mode", "with_residual", "interpret"))
def lorenzo_quant(data: jax.Array, eb: jax.Array, *, code_mode: str = "sign_mag",
                  with_residual: bool = False, interpret: bool = False):
    """float (1-3)D -> u16 codes, identical to ref.lorenzo_quant_ref.

    With ``with_residual`` it returns ``(codes, residual)``: the int32
    ``delta - decode(code)`` of ``core.quant.to_codes``, data-shaped.

    1D inputs are reshaped to (rows, ROW_1D) with the cross-row boundary
    handled inside the kernel, so the difference stream matches the flat
    reference.
    """
    shape = data.shape
    ndim = data.ndim
    if ndim > 3:
        raise ValueError(f"Lorenzo kernel supports 1-3D, got {ndim}D")
    # sub-f32 floats stay native (halved HBM traffic, doubled bands); the
    # exact widening cast to f32 happens inside the kernel (_prequant)
    x = data if (jnp.issubdtype(data.dtype, jnp.floating)
                 and data.dtype.itemsize <= 4) else data.astype(jnp.float32)
    view, band, hblock, hrow = _geometry(shape, x.dtype.itemsize)
    if ndim == 1:
        x = jnp.pad(x, (0, view[0] * ROW_1D - x.size)).reshape(view)
    lead = view[0]
    bands = (lead + band - 1) // band
    x = jnp.pad(x, [(0, bands * band - lead)] + [(0, 0)] * (x.ndim - 1))
    trailing = x.shape[1:]
    zeros_trail = (0,) * len(trailing)
    per_band = band // hblock           # halo blocks per band

    def band_index(i):
        return (i, *zeros_trail)

    def halo_index(i):
        # the block holding row i*band - 1 (clamped; masked on band 0)
        return (jnp.maximum(i * per_band - 1, 0), *zeros_trail)

    step = _quant.step_scalars(eb)
    band_spec = pl.BlockSpec((band, *trailing), band_index)
    out_shape = [jax.ShapeDtypeStruct(x.shape, jnp.uint16)]
    if with_residual:
        out_shape.append(jax.ShapeDtypeStruct(x.shape, jnp.int32))
    outs = pl.pallas_call(
        _make_kernel(1 if ndim == 1 else ndim, code_mode, hrow, with_residual),
        grid=(bands,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  band_spec,
                  pl.BlockSpec((hblock, *trailing), halo_index)],
        out_specs=[band_spec] * len(out_shape),
        out_shape=out_shape,
        # bands are independent (the halo is a read-only input view, no
        # cross-step scratch): declared parallel deliberately
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(step, x, x)

    if ndim == 1:
        outs = [o.reshape(-1)[: shape[0]] for o in outs]
    else:
        outs = [o[: shape[0]] for o in outs]
    return tuple(outs) if with_residual else outs[0]


# ---------------------------------------------------------------------------
# Static-analysis declaration (repro.analysis): mirrors the launch above
# ---------------------------------------------------------------------------

@register_spec("lorenzo_quant")
def kernel_spec(shape: tuple[int, ...], dtype: str = "float32") -> KernelSpec:
    """KernelSpec for ``lorenzo_quant`` at one (shape, dtype) point."""
    itemsize = {"float32": 4, "bfloat16": 2, "float16": 2}[dtype]
    view, band, hblock, hrow = _geometry(tuple(shape), itemsize)
    lead, trailing = view[0], view[1:]
    bands = -(-lead // band)
    band_block = (band, *trailing)
    zeros_trail = (0,) * len(trailing)
    per_band = band // hblock
    return KernelSpec(
        name="lorenzo_quant", module=__name__, grid=(bands,),
        in_blocks=(
            BlockDecl("step", (3,), "float32", memory=SMEM,
                      index_map=lambda i: (0,)),
            BlockDecl("x", band_block, dtype,
                      index_map=lambda i: (i, *zeros_trail)),
            BlockDecl("halo", (hblock, *trailing), dtype,
                      index_map=lambda i: (max(i * per_band - 1, 0),
                                           *zeros_trail)),
        ),
        out_blocks=(
            BlockDecl("codes", band_block, "uint16",
                      index_map=lambda i: (i, *zeros_trail)),
        ),
        dimension_semantics=("parallel",),
        kernel_fn=_make_kernel(1 if len(shape) == 1 else len(shape),
                               "sign_mag", hrow),
        point=f"shape={shape} dtype={dtype} band={band}")
