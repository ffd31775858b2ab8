"""Single-launch fused FZ decompress megakernel (decode mirror of §3.5).

One ``pallas_call`` runs the ENTIRE decompression pipeline — bit-flag unpack +
offset-gather block decode + bitunshuffle + code→delta conversion + inverse
Lorenzo + dequantization — so neither the u16 word stream nor the code stream
ever touches HBM. The reference path materializes both (plus a global
``cumsum`` over all flags for the payload offsets); here the running payload
read offset rides in SMEM scratch across the TPU grid's *sequential* steps,
so each step's offsets are ``smem_offset + local exclusive cumsum`` — no
global scan, no gather over a materialized stream.

Stream geometry is the compress kernel's :class:`StreamPlan`: the decoder
walks the same leading-axis bands, holding the < TILE decoded-but-unconsumed
codes of each step in a right-aligned VMEM carry. The inverse-Lorenzo
leading-axis integration threads through scratch as well: per-axis prefix
sums commute, so each band only needs the previous band's last cumulative
row/plane (a ``(1, *trailing)`` i32 VMEM carry; for the flattened-1D layout a
single SMEM scalar), and all trailing-axis cumsums stay band-internal. 2D/3D
trailing-axis cumsums therefore run in-kernel too — no XLA epilogue was
needed in interpret mode; if Mosaic layouts fight the in-kernel trailing
cumsum on real TPU, peeling it back out is a one-line split (tracked with the
TPU hillclimb item in ROADMAP.md).

Exact-outlier residuals (the beyond-paper strict-bound channel) are applied
in-kernel: each band scatter-adds the residuals whose flat index lands in its
range into its delta slice (an extra trash column absorbs out-of-band and
unused slots, whose values are zero by construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernelspec import (BlockDecl, KernelSpec, ScratchDecl,
                                       register_spec)
from repro.core import quant as _quant
from . import bitshuffle_flag as _bsf
from .fused_compress import (BLOCK_WORDS, BLOCKS_PER_TILE, FLAG_WORDS_PER_TILE,
                             GROUP, GROUPS_PER_TILE, ROW_1D, TILE, StreamPlan,
                             _capacity_for, plan_stream)


def _unshuffle_tiles(words: jax.Array, wmax: int) -> jax.Array:
    """(wmax, TILE) u16 shuffled words -> (wmax*TILE,) u16 codes."""
    planes = words.reshape(wmax, GROUP, GROUPS_PER_TILE)
    t = jnp.swapaxes(planes, 1, 2)
    return _bsf.transpose16_inkernel(t).reshape(wmax * TILE)


def _inverse_lorenzo_band(delta: jax.Array, p: StreamPlan, qcarry_ref, sm_ref,
                          is_first):
    """Band delta (1, m) i32 -> band q (band, *trailing) i32, carrying the
    leading-axis integration through scratch. Trailing-axis prefix sums are
    band-internal (per-axis cumsums commute)."""
    if p.kern_nd == 1:
        rows = delta.reshape(p.band, ROW_1D)
        rs = jnp.cumsum(rows, axis=1)
        tot = rs[:, -1:]
        base = sm_ref[3] + jnp.cumsum(tot, axis=0) - tot       # exclusive
        q = rs + base
        sm_ref[3] = q[-1, -1]
        return q
    e = delta.reshape(p.band, *p.trailing)
    for ax in range(len(p.trailing), 0, -1):
        e = jnp.cumsum(e, axis=ax)
    carry = jnp.where(is_first, jnp.zeros_like(qcarry_ref[...]), qcarry_ref[...])
    q = jnp.cumsum(e, axis=0) + carry
    qcarry_ref[...] = q[-1:]
    return q


def _make_decode_kernel(p: StreamPlan, capacity: int, code_mode: str,
                        n_outliers: int):
    m, wmax = p.m, p.wmax_decode
    nb = wmax * BLOCKS_PER_TILE

    def kernel(*refs):
        if n_outliers:
            (bitflags_ref, payload_ref, eb_ref, oidx_ref, oval_ref,
             out_ref, carry_ref, qcarry_ref, sm_ref) = refs
        else:
            (bitflags_ref, payload_ref, eb_ref,
             out_ref, carry_ref, qcarry_ref, sm_ref) = refs
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            sm_ref[0] = 0                        # carry length (codes)
            sm_ref[1] = 0                        # running payload read offset
            sm_ref[2] = 0                        # tiles consumed so far
            sm_ref[3] = 0                        # 1D inverse-Lorenzo carry
            carry_ref[...] = jnp.zeros((1, TILE), jnp.uint16)

        carry_len = sm_ref[0]
        w = (m - carry_len + TILE - 1) // TILE   # tiles to open this step
        tiles_done = sm_ref[2]

        # unpack this step's candidate flags (wmax tiles' worth; the input is
        # zero-padded past the real flag words, so over-reads decode to zero)
        fw = bitflags_ref[0, pl.ds(tiles_done * FLAG_WORDS_PER_TILE,
                                   wmax * FLAG_WORDS_PER_TILE)]
        bits = (fw.reshape(nb // 32, 1) >>
                jax.lax.broadcasted_iota(jnp.uint32, (nb // 32, 32), 1)) & 1
        flags = bits.reshape(nb).astype(bool)
        tile_of = jax.lax.broadcasted_iota(
            jnp.int32, (wmax, BLOCKS_PER_TILE), 0).reshape(nb)
        fv = flags & (tile_of < w)               # beyond-w tiles stay unread

        # offset-gather decode at smem_offset + local exclusive cumsum
        fv_i = fv.astype(jnp.int32).reshape(1, nb)
        excl = (jnp.cumsum(fv_i, axis=1) - fv_i).reshape(nb)
        off = sm_ref[1] + excl
        in_cap = fv & (off < capacity)
        rows = payload_ref[:, jnp.minimum(off, capacity - 1)].T
        blocks = jnp.where(in_cap[:, None], rows, jnp.uint16(0))
        codes = _unshuffle_tiles(blocks.reshape(wmax, TILE), wmax)

        # right-aligned code carry, same discipline as the compress kernel
        buf = jnp.concatenate([carry_ref[...], codes.reshape(1, -1)], axis=1)
        band_codes = jax.lax.dynamic_slice(
            buf, (0, TILE - carry_len), (1, m))
        carry_ref[...] = jax.lax.dynamic_slice(buf, (0, w * TILE), (1, TILE))
        sm_ref[0] = carry_len + w * TILE - m
        sm_ref[1] += jnp.sum(fv_i, dtype=jnp.int32)
        sm_ref[2] = tiles_done + w

        delta = _quant.from_codes(band_codes, code_mode=code_mode)
        if n_outliers:
            # residuals whose flat index lands in this band; unused slots
            # carry value 0 so stray in-range fill indices are harmless
            local = oidx_ref[...].reshape(n_outliers) - i * m
            ok = (local >= 0) & (local < m)
            tgt = jnp.where(ok, local, m)        # column m = trash slot
            ext = jnp.concatenate(
                [delta, jnp.zeros((1, 1), jnp.int32)], axis=1)
            ext = ext.at[0, tgt].add(
                jnp.where(ok, oval_ref[...].reshape(n_outliers), 0))
            delta = ext[:, :m]

        q = _inverse_lorenzo_band(delta, p, qcarry_ref, sm_ref, i == 0)
        out_ref[...] = q.reshape(p.band, *p.trailing).astype(jnp.float32) \
            * (2.0 * eb_ref[0, 0])

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("shape", "code_mode", "interpret"))
def fused_decompress(bitflags: jax.Array, payload: jax.Array, eb: jax.Array,
                     *, shape: tuple[int, ...], code_mode: str = "sign_mag",
                     outlier_idx: jax.Array | None = None,
                     outlier_val: jax.Array | None = None,
                     interpret: bool = False) -> jax.Array:
    """Container fields -> float32[shape], whole inverse pipeline in-kernel.

    Bit-identical to ``dual_dequantize(bitunshuffle(decode(...)))`` including
    the optional exact-outlier residual channel.
    """
    p = plan_stream(tuple(shape))
    capacity = payload.shape[1]
    wmax = p.wmax_decode
    # flag words the decoder may touch: every band opens at most wmax tiles
    need = (-(-p.bands * p.m // TILE) + wmax) * FLAG_WORDS_PER_TILE
    bf = jnp.pad(bitflags.reshape(1, -1),
                 ((0, 0), (0, max(0, need - bitflags.size))))

    n_outliers = 0 if outlier_idx is None else int(outlier_idx.size)
    band_block = (p.band, *p.trailing)
    zeros_trail = (0,) * len(p.trailing)
    in_specs = [pl.BlockSpec((1, bf.shape[1]), lambda i: (0, 0)),
                pl.BlockSpec((BLOCK_WORDS, capacity), lambda i: (0, 0)),
                pl.BlockSpec((1, 1), lambda i: (0, 0))]
    args = [bf, payload, jnp.reshape(jnp.asarray(eb, jnp.float32), (1, 1))]
    if n_outliers:
        in_specs += [pl.BlockSpec((1, n_outliers), lambda i: (0, 0))] * 2
        args += [outlier_idx.reshape(1, -1).astype(jnp.int32),
                 outlier_val.reshape(1, -1).astype(jnp.int32)]

    qcarry_shape = (1, *p.trailing) if p.kern_nd > 1 else (1, 1)
    out = pl.pallas_call(
        _make_decode_kernel(p, capacity, code_mode, n_outliers),
        grid=(p.bands,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(band_block, lambda i: (i, *zeros_trail)),
        out_shape=jax.ShapeDtypeStruct((p.bands * p.band, *p.trailing),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, TILE), jnp.uint16),
                        pltpu.VMEM(qcarry_shape, jnp.int32),
                        pltpu.SMEM((4,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
    if p.kern_nd == 1:
        return out.reshape(-1)[: p.n]
    return out[: p.lead]


# ---------------------------------------------------------------------------
# Static-analysis declaration (repro.analysis): mirrors the launch above
# ---------------------------------------------------------------------------

@register_spec("fused_decode")
def kernel_spec(shape: tuple[int, ...],
                capacity_frac: float = 1.0) -> KernelSpec:
    p = plan_stream(tuple(shape))
    capacity = _capacity_for(p.n, capacity_frac)
    wmax = p.wmax_decode
    need = (-(-p.bands * p.m // TILE) + wmax) * FLAG_WORDS_PER_TILE
    zeros_trail = (0,) * len(p.trailing)
    qcarry_shape = (1, *p.trailing) if p.kern_nd > 1 else (1, 1)
    return KernelSpec(
        name="fused_decode", module=__name__, grid=(p.bands,),
        in_blocks=(
            BlockDecl("bitflags", (1, max(need, 1)), "uint32",
                      index_map=lambda i: (0, 0)),
            BlockDecl("payload", (BLOCK_WORDS, capacity), "uint16",
                      index_map=lambda i: (0, 0)),
            BlockDecl("eb", (1, 1), "float32", index_map=lambda i: (0, 0)),
        ),
        out_blocks=(
            BlockDecl("out", (p.band, *p.trailing), "float32",
                      index_map=lambda i: (i, *zeros_trail)),
        ),
        scratch=(ScratchDecl("carry", (1, TILE), "uint16", "vmem"),
                 ScratchDecl("qcarry", qcarry_shape, "int32", "vmem"),
                 ScratchDecl("sm", (4,), "int32", "smem")),
        dimension_semantics=("arbitrary",),
        kernel_fn=_make_decode_kernel(p, capacity, "sign_mag", 0),
        point=(f"shape={tuple(shape)} capacity_frac={capacity_frac} "
               f"capacity={capacity}"))
