"""Fast zero-block lossless encoder (FZ-GPU §3.4), pure-JAX reference semantics.

Phase 1: partition the bitshuffled u16 stream into 16-byte blocks (8 words),
flag non-zero blocks, and pack the flags into a bit-flag array (max CR = 128,
matching the paper). In the production path phase 1 is fused into the
bitshuffle Pallas kernel exactly as the paper fuses it into the CUDA kernel.

Phase 2: exclusive prefix-sum of the flags gives each surviving block its
output offset; compaction copies surviving blocks to the payload. TPU
adaptation: CUB ``ExclusiveSum`` -> a blocked XLA scan
(:func:`exclusive_cumsum`); the scatter-style CUDA compaction -> an index
scatter of the surviving blocks' positions followed by one gather of their
words, which is the TPU-friendly direction.

JAX static shapes require a fixed payload *capacity*; ``nnz_blocks`` reports
the used prefix, and byte accounting uses exact used bytes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs

BLOCK_WORDS = 8          # u16 words per zero-detection block (16 bytes)
BLOCK_BYTES = 2 * BLOCK_WORDS
FLAGS_PER_WORD = 32      # bit flags packed per u32


def flag_words(n_blocks: int) -> int:
    """u32 words in the packed bit-flag array: ceil(n_blocks / 32).

    This is the stored form — what ``pack_bitflags`` produces and what v1
    serialized containers carry verbatim (docs/CONTAINER_FORMAT.md);
    ``used_bytes`` models the ideal (n_blocks+7)//8 bit packing for ratio
    accounting.
    """
    return -(-n_blocks // FLAGS_PER_WORD)


def block_flags(shuffled: jax.Array) -> jax.Array:
    """(n_words,) u16 -> (n_blocks,) bool non-zero flags."""
    if shuffled.size % BLOCK_WORDS:
        raise ValueError(f"{shuffled.size} words not a multiple of {BLOCK_WORDS}")
    return jnp.any(shuffled.reshape(-1, BLOCK_WORDS) != 0, axis=-1)


def pack_bitflags(flags: jax.Array) -> jax.Array:
    """(n_blocks,) bool -> (ceil(n/32),) u32 bit-flag array (LSB-first)."""
    n = flags.size
    pad = (-n) % FLAGS_PER_WORD
    f = jnp.pad(flags, (0, pad)).reshape(-1, FLAGS_PER_WORD).astype(jnp.uint32)
    return jnp.sum(f << jnp.arange(FLAGS_PER_WORD, dtype=jnp.uint32), axis=-1, dtype=jnp.uint32)


def unpack_bitflags(bitflags: jax.Array, n_blocks: int) -> jax.Array:
    """(W,) u32 -> (n_blocks,) bool."""
    bits = (bitflags[:, None] >> jnp.arange(FLAGS_PER_WORD, dtype=jnp.uint32)) & 1
    return bits.reshape(-1)[:n_blocks].astype(bool)


def word_major(shuffled: jax.Array) -> jax.Array:
    """Flat u16 word stream -> (8, n_blocks): row j is word j of every block.

    The layout payloads and gathers use. Block-major rows of 8 words would
    be padded 16x by the TPU's (8, 128) tiling; word-major keeps the blocks
    on lanes.
    """
    return shuffled.reshape(-1, BLOCK_WORDS).T


SCAN_ROW = 512           # row width of the blocked prefix sum


def exclusive_cumsum(x: jax.Array) -> jax.Array:
    """``jnp.cumsum(x) - x`` for a 1D int32 array, as a two-level scan:
    prefix sums within rows of ``SCAN_ROW``, then over the row totals.

    Same values; the point is the TPU compiler, which takes about 30 s to
    compile a flat cumsum of ~1M elements and about 2 s for this form at
    every size (AOT compiles for a v5e).
    """
    n = x.size
    rows = jnp.pad(x, (0, (-n) % SCAN_ROW)).reshape(-1, SCAN_ROW)
    inner = jnp.cumsum(rows, axis=1)
    total = inner[:, -1]
    out = inner - rows + (jnp.cumsum(total) - total)[:, None]
    return out.reshape(-1)[:n]


def compact_blocks(flags: jax.Array, words: jax.Array, *, capacity: int):
    """XLA phase-2 compaction: (flags bool[n_blocks], words u16[8, n_blocks])
    -> (bitflags u32[W], payload u16[8, capacity], nnz i32[]).

    The scan + index-scatter + gather formulation, shared by :func:`encode`
    and the staged kernel path (``kernels.ops.bitshuffle_flag_encode``). The fused
    megakernel (kernels/fused_compress.py) replaces this wholesale with an
    in-kernel running-offset scatter; this stays as its oracle.
    """
    with obs.span("fz.stage.compact_blocks"):
        nnz = jnp.sum(flags, dtype=jnp.int32)
        # src[k] = index of the k-th flagged block (0 past nnz), as
        # jnp.nonzero(flags, size=capacity, fill_value=0)
        n = flags.size
        dst = jnp.where(flags, exclusive_cumsum(flags.astype(jnp.int32)), capacity)
        src = jnp.zeros((capacity,), jnp.int32).at[dst].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        payload = words[:, src]
        # slots past nnz replicate block 0; zero them so payload is deterministic
        payload = jnp.where(jnp.arange(capacity)[None, :] < nnz, payload, 0)
        return pack_bitflags(flags), payload.astype(jnp.uint16), nnz


@partial(jax.jit, static_argnames=("capacity",))
def encode(shuffled: jax.Array, *, capacity: int):
    """Compact non-zero blocks of a flat shuffled word stream.

    Returns (bitflags u32[W], payload u16[8, capacity], nnz i32[]): payload
    column k is the k-th surviving 16-byte block. Blocks beyond ``capacity``
    are dropped (callers size capacity = n_blocks for lossless-by-construction,
    or smaller for bounded wire formats with a raw fallback; the dropped
    count is nnz - capacity when positive).
    """
    words = word_major(shuffled)
    flags = jnp.any(words != 0, axis=0)
    return compact_blocks(flags, words, capacity=capacity)


@partial(jax.jit, static_argnames=("n_blocks",))
def decode_blocks(bitflags: jax.Array, payload: jax.Array, *,
                  n_blocks: int) -> jax.Array:
    """Inverse of :func:`encode` -> word-major stream u16[8, n_blocks].

    Offsets are the exclusive prefix sum of the unpacked flags; each flagged
    block gathers its payload column, unflagged blocks are zero. Blocks whose
    offset exceeded capacity at encode time decode to zero (bounded-capacity
    wire mode; exact when capacity >= nnz).
    """
    flags = unpack_bitflags(bitflags, n_blocks)
    offsets = exclusive_cumsum(flags.astype(jnp.int32))
    cap = payload.shape[1]
    in_cap = flags & (offsets < cap)
    words = payload[:, jnp.minimum(offsets, cap - 1)]
    return jnp.where(in_cap[None, :], words, 0).astype(jnp.uint16)


def decode(bitflags: jax.Array, payload: jax.Array, *, n_blocks: int) -> jax.Array:
    """:func:`decode_blocks` as the flat u16 word stream (n_blocks * 8)."""
    return decode_blocks(bitflags, payload, n_blocks=n_blocks).T.reshape(-1)


def used_bytes(n_blocks: int, nnz: jax.Array, n_outliers: jax.Array | None = None,
               header_bytes: int = 32) -> jax.Array:
    """Exact compressed size in bytes (header + bitflags + blocks + outliers).

    int32 arithmetic: valid for per-leaf tensors < 2 GiB compressed, which the
    tree helpers guarantee by compressing leaf-wise.
    """
    flag_bytes = (n_blocks + 7) // 8
    out = header_bytes + flag_bytes + nnz.astype(jnp.int32) * BLOCK_BYTES
    if n_outliers is not None:
        out = out + n_outliers.astype(jnp.int32) * 8  # 4B idx + 4B residual
    return out
