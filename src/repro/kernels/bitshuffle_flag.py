"""Fused bitshuffle + zero-block flagging Pallas TPU kernel (paper §3.3-3.4).

Mirrors FZ-GPU's fused CUDA kernel: one pass over the quantization codes in
fast memory produces BOTH the bitshuffled stream and the per-16-byte-block
zero flags, eliminating the extra HBM round-trip the paper eliminates with
shared memory (their Figure 10 "bitshuffle-mark-v2").

TPU adaptation (DESIGN.md §2):
  * warp ballot -> 4-stage masked-swap 16x16 bit-matrix transpose, expressed
    with shifts/masks and rotates, i.e. no gathers, fully VPU-vectorizable;
  * 32x33 padded shared memory -> VMEM tiles via BlockSpec; no banking.

Block layout: each grid step processes ``block_tiles(n)`` tiles of TILE=4096
u16 codes — 128 (one tile per lane after the in-kernel transpose) once the
stream has that many. VMEM per step at 128 tiles: in 1 MiB + out 1 MiB +
flags 64 KiB, double-buffered, plus a 2 MiB i32 scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernelspec import (BlockDecl, KernelSpec, ScratchDecl,
                                       register_spec)

TILE = 4096
GROUP = 16
GROUPS_PER_TILE = TILE // GROUP          # 256
BLOCK_WORDS = 8                          # words per zero-flag block (16 B)
BLOCKS_PER_TILE = TILE // BLOCK_WORDS    # 512
MAX_BLOCK_TILES = 128                    # tiles per grid step (lane width)

_STAGES = ((8, 0xFF00), (4, 0xF0F0), (2, 0xCCCC), (1, 0xAAAA))


def transpose16_inkernel(x: jax.Array, axis: int = -1) -> jax.Array:
    """Masked-swap bit-matrix transpose of 16-element groups along ``axis``
    (involution; ``x`` holds u16 values in any integer dtype).

    The partner of element i is element i XOR delta, fetched with two
    rotates and a select on the element index — no gather and no reversal,
    both of which Mosaic refuses.
    """
    axis = axis % x.ndim
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    for delta, mask in _STAGES:
        lo = ~mask & 0xFFFF
        low_half = (idx & delta) == 0
        partner = jnp.where(low_half, pltpu.roll(x, n - delta, axis),
                            pltpu.roll(x, delta, axis))
        hi_val = (x & mask) | ((partner & mask) >> delta)
        lo_val = ((partner & lo) << delta) | (x & lo)
        x = jnp.where(low_half, hi_val, lo_val)
    return x


# Both kernels work on a block of tiles transposed so that each tile is one
# lane column: row r of the (TILE, tiles) block is code r of every tile. The
# bit transpose then runs along sublanes, and the word reorders between
# group-major (g*16 + p), plane-major (p*256 + g) and word-major order are
# strided row copies through VMEM scratch.
#
# The shuffled stream leaves and enters HBM *word-major*: array
# (BLOCK_WORDS, n_tiles, BLOCKS_PER_TILE), element [j, t, b] = word j of
# zero-flag block b of tile t. Block-major (n_blocks, 8) rows would be padded
# 16x by the TPU's (8, 128) tiling; word-major keeps the blocks on lanes for
# the compaction gather (core.encode.compact_blocks) and its inverse.

def _bitshuffle_flag_kernel(codes_ref, shuffled_ref, flags_ref, buf_ref):
    """codes (TB, TILE) u16 -> shuffled (8, TB, 512) u16 word-major,
    flags (TB, 512) u8."""
    buf_ref[...] = transpose16_inkernel(
        codes_ref[...].astype(jnp.int32).T, axis=0)    # row g*16 + p
    planes = jnp.concatenate(
        [buf_ref[pl.ds(p, GROUPS_PER_TILE, stride=GROUP), :]
         for p in range(GROUP)], axis=0)               # row p*256 + g
    buf_ref[...] = planes                              # row b*8 + j
    for j in range(BLOCK_WORDS):
        shuffled_ref[j] = buf_ref[pl.ds(j, BLOCKS_PER_TILE, stride=BLOCK_WORDS),
                                  :].T.astype(jnp.uint16)
    # fused phase-1 of the encoder: zero flags per 8-word block
    nz = jnp.max(planes.reshape(BLOCKS_PER_TILE, BLOCK_WORDS, -1), axis=1)
    flags_ref[...] = (nz.T != 0).astype(jnp.uint8)


def _unshuffle_kernel(shuffled_ref, codes_ref, words_ref, buf_ref):
    """shuffled (8, TB, 512) u16 word-major -> codes (TB, TILE) u16."""
    for j in range(BLOCK_WORDS):
        words_ref[pl.ds(j, BLOCKS_PER_TILE, stride=BLOCK_WORDS), :] = \
            shuffled_ref[j].astype(jnp.int32).T        # row b*8 + j
    for p in range(GROUP):
        buf_ref[pl.ds(p, GROUPS_PER_TILE, stride=GROUP), :] = \
            words_ref[pl.ds(p * GROUPS_PER_TILE, GROUPS_PER_TILE), :]
    codes_ref[...] = transpose16_inkernel(
        buf_ref[...], axis=0).T.astype(jnp.uint16)     # from row g*16 + p


def block_tiles(n_tiles: int) -> int:
    """Tiles per grid step: a full 128-lane block, or every tile (rounded
    up to a 16-row multiple of packed u16) when there are fewer."""
    return min(MAX_BLOCK_TILES, -(-max(n_tiles, 1) // 16) * 16)


def _pad_tiles(n_tiles: int) -> int:
    tb = block_tiles(n_tiles)
    return -(-max(n_tiles, 1) // tb) * tb


_WORD_MAJOR = lambda tb: pl.BlockSpec((BLOCK_WORDS, tb, BLOCKS_PER_TILE),
                                      lambda i: (0, i, 0))
_TILES = lambda tb, w: pl.BlockSpec((tb, w), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitshuffle_flag(codes_tiles: jax.Array, *, interpret: bool = False):
    """(n_tiles, TILE) u16 -> (shuffled (8, n_tiles, 512) u16 word-major,
    flags (n_tiles, 512) u8)."""
    n_tiles = codes_tiles.shape[0]
    tb, padded = block_tiles(n_tiles), _pad_tiles(n_tiles)
    x = jnp.pad(codes_tiles, ((0, padded - n_tiles), (0, 0)))
    shuffled, flags = pl.pallas_call(
        _bitshuffle_flag_kernel,
        grid=(padded // tb,),
        in_specs=[_TILES(tb, TILE)],
        out_specs=[_WORD_MAJOR(tb), _TILES(tb, BLOCKS_PER_TILE)],
        out_shape=[jax.ShapeDtypeStruct((BLOCK_WORDS, padded, BLOCKS_PER_TILE),
                                        jnp.uint16),
                   jax.ShapeDtypeStruct((padded, BLOCKS_PER_TILE), jnp.uint8)],
        scratch_shapes=[pltpu.VMEM((TILE, tb), jnp.int32)],
        # per-step tiles are independent: parallel by declaration, not default
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return shuffled[:, :n_tiles], flags[:n_tiles]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitunshuffle_tiles(shuffled: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(8, n_tiles, 512) u16 word-major shuffled -> (n_tiles, TILE) codes."""
    n_tiles = shuffled.shape[1]
    tb, padded = block_tiles(n_tiles), _pad_tiles(n_tiles)
    x = jnp.pad(shuffled, ((0, 0), (0, padded - n_tiles), (0, 0)))
    codes = pl.pallas_call(
        _unshuffle_kernel,
        grid=(padded // tb,),
        in_specs=[_WORD_MAJOR(tb)],
        out_specs=_TILES(tb, TILE),
        out_shape=jax.ShapeDtypeStruct((padded, TILE), jnp.uint16),
        scratch_shapes=[pltpu.VMEM((TILE, tb), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return codes[:n_tiles]


# ---------------------------------------------------------------------------
# Static-analysis declarations (repro.analysis): mirror the launches above
# ---------------------------------------------------------------------------

def _grid_of(n_tiles: int) -> int:
    return _pad_tiles(n_tiles) // block_tiles(n_tiles)


@register_spec("bitshuffle_flag.shuffle")
def _shuffle_spec(n_tiles: int) -> KernelSpec:
    tb = block_tiles(n_tiles)
    return KernelSpec(
        name="bitshuffle_flag.shuffle", module=__name__,
        grid=(_grid_of(n_tiles),),
        in_blocks=(BlockDecl("codes", (tb, TILE), "uint16",
                             index_map=lambda i: (i, 0)),),
        out_blocks=(BlockDecl("shuffled", (BLOCK_WORDS, tb, BLOCKS_PER_TILE),
                              "uint16", index_map=lambda i: (0, i, 0)),
                    BlockDecl("flags", (tb, BLOCKS_PER_TILE), "uint8",
                              index_map=lambda i: (i, 0))),
        scratch=(ScratchDecl("buf", (TILE, tb), "int32", "vmem"),),
        dimension_semantics=("parallel",),
        kernel_fn=_bitshuffle_flag_kernel,
        point=f"n_tiles={n_tiles}")


@register_spec("bitshuffle_flag.unshuffle")
def _unshuffle_spec(n_tiles: int) -> KernelSpec:
    tb = block_tiles(n_tiles)
    return KernelSpec(
        name="bitshuffle_flag.unshuffle", module=__name__,
        grid=(_grid_of(n_tiles),),
        in_blocks=(BlockDecl("shuffled", (BLOCK_WORDS, tb, BLOCKS_PER_TILE),
                             "uint16", index_map=lambda i: (0, i, 0)),),
        out_blocks=(BlockDecl("codes", (tb, TILE), "uint16",
                              index_map=lambda i: (i, 0)),),
        scratch=(ScratchDecl("words", (TILE, tb), "int32", "vmem"),
                 ScratchDecl("buf", (TILE, tb), "int32", "vmem")),
        dimension_semantics=("parallel",),
        kernel_fn=_unshuffle_kernel,
        point=f"n_tiles={n_tiles}")
