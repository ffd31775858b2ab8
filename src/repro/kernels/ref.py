"""Pure-jnp oracles for every Pallas kernel in this package.

These re-export / thin-wrap the core reference implementations so kernel
tests have a single import point, and add the fused-output oracles (the fused
kernels emit multiple results per pass; the oracle composes the unfused
reference stages to produce identical outputs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import encode as _enc
from repro.core import quant as _quant
from repro.core import shuffle as _shuffle

TILE = _shuffle.TILE
BLOCK_WORDS = _enc.BLOCK_WORDS
BLOCKS_PER_TILE = TILE // BLOCK_WORDS  # 512


def lorenzo_quant_ref(data: jax.Array, eb: jax.Array, *, code_mode: str = "sign_mag") -> jax.Array:
    """Fused pre-quantization + Lorenzo + sign-magnitude codes (paper mode:
    saturating, no outlier channel)."""
    q = _quant.prequantize(data, eb)
    delta = _quant.lorenzo_delta(q)
    codes, _, _ = _quant.to_codes(delta, code_mode=code_mode)
    return codes


def bitshuffle_flag_ref(codes_tiles: jax.Array):
    """Fused bitshuffle + zero-block byte flags.

    codes_tiles: (n_tiles, TILE) u16.
    Returns (shuffled (8, n_tiles, 512) u16 word-major — [j, t, b] is word j
    of block b of tile t — and byteflags (n_tiles, 512) u8), where byteflag b
    of tile t covers shuffled words [8b, 8b+8) of tile t.
    """
    n_tiles = codes_tiles.shape[0]
    shuffled = _shuffle.bitshuffle(codes_tiles.reshape(-1))
    blocks = shuffled.reshape(n_tiles, BLOCKS_PER_TILE, BLOCK_WORDS)
    flags = jnp.any(blocks != 0, axis=-1)
    return blocks.transpose(2, 0, 1), flags.astype(jnp.uint8)


def bitunshuffle_ref(shuffled: jax.Array) -> jax.Array:
    """(8, n_tiles, 512) u16 word-major -> (n_tiles, TILE) u16 codes."""
    n_tiles = shuffled.shape[1]
    flat = shuffled.transpose(1, 2, 0).reshape(-1)
    return _shuffle.bitunshuffle(flat).reshape(n_tiles, TILE)


def dequant_lorenzo_ref(codes: jax.Array, eb: jax.Array, shape, *,
                        code_mode: str = "sign_mag") -> jax.Array:
    """Inverse fused kernel oracle: codes -> float reconstruction."""
    return _quant.dual_dequantize(codes, eb, tuple(shape), code_mode=code_mode)
