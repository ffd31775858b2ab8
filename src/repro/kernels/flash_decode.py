"""Block-parallel Pallas flash-decode kernel (contiguous + paged layouts).

Decode attention over a long KV cache is the movement-bound serving hot path
(same class of kernel FZ-GPU optimizes in §3.3: all bandwidth, no reuse). The
jnp reference in ``dist/flash_decode.decode_partials`` recomputes the full
(B, KVH, G, S) score matrix in HBM; this kernel tiles the KV sequence axis
and keeps the online-softmax state on-chip:

  * grid = (B, T): one grid step per (batch row, KV tile). T is the last grid
    axis, so tiles of one row run back-to-back and the partials accumulate in
    the revisited output block (standard Pallas accumulation: the out
    BlockSpec index map ignores ``t``, so the block stays resident in VMEM
    across the whole row).
  * per tile: s = q @ k_tile^T, masked by the valid prefix, then the running
    (max, exp-sum, weighted-value) triple is rescaled and accumulated — the
    same math as ``dist/flash_decode.decode_partials``, but per tile with the
    cross-tile combine fused on-chip instead of one S-wide softmax.
  * tile geometry: KV_TILE = 128 positions per step (lane-aligned on TPU; any
    divisor works in interpret mode). VMEM per grid step is the k/v tiles —
    2 * KV_TILE * KVH * hd elements — plus the (KVH, G)-shaped state, far
    under a v5e core's budget for every geometry in this repo.

Two entry points share the one kernel body:

  * ``decode_partials`` — contiguous (B, S, KVH, D) caches, reshaped for free
    into (B, T, KV_TILE, KVH, D) tiles (row-major adjacency preserved);
  * ``decode_partials_pages`` — the kvpool slab layout (B, P, ps, KVH, D)
    consumed *directly*: a page is a tile, no contiguous materialization.

Both return the ``(m, num, den)`` triple of the jnp reference and are its
oracle-pinned drop-ins (tests/test_kernels.py, 2e-4); ``shard_offset`` is
folded into the length mask (``pos < length - offset``) so the sequence-
sharded combine in ``dist/flash_decode.flash_decode_shard`` works unchanged.
Like kernels/ops.py, non-TPU backends run through the Pallas interpreter.

Empty-slice contract (inherited from the jnp reference): a fully-masked
slice yields m == NEG_INF and num == den == 0. The combined output is 0
because num and den are 0 — NOT because the renorm weight vanishes; when
*every* slice is empty the renorm weight is exp(NEG_INF - NEG_INF) == 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernelspec import (SMEM, BlockDecl, KernelSpec,
                                       register_spec)

NEG_INF = -1e30        # same finite stand-in as dist/flash_decode.py
KV_TILE = 128          # default KV positions per grid step (TPU lane width)


def _interpret() -> bool:
    from . import ops
    return ops.backend_interpret()   # the package's one backend check


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, m_ref, num_ref, den_ref,
                         *, tile: int):
    """One (batch row, KV tile) grid step of the online softmax.

    len_ref: (B,) i32 effective valid lengths in SMEM (scalar prefetch,
    already offset-adjusted);
    q_ref: (1, KVH, G, D) f32 pre-scaled query; k_ref/v_ref: (1, 1, tile,
    KVH, D) cache tile; m/num/den refs: the (1, KVH, G[, D]) f32 partials,
    revisited across every tile of the row and accumulated in place.
    """
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[0] = jnp.full(m_ref.shape[1:], NEG_INF, jnp.float32)
        num_ref[0] = jnp.zeros(num_ref.shape[1:], jnp.float32)
        den_ref[0] = jnp.zeros(den_ref.shape[1:], jnp.float32)

    length = len_ref[b]
    q = q_ref[0]                                     # (KVH, G, D) f32
    k = k_ref[0, 0].astype(jnp.float32)              # (tile, KVH, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.einsum("hgd,khd->hgk", q, k)             # (KVH, G, tile)
    pos = t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
    valid = pos < length
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(valid[None, None, :], p, 0.0)      # empty-tile safety
    corr = jnp.exp(m_prev - m_new)                   # 1 while both are NEG_INF
    m_ref[0] = m_new
    den_ref[0] = den_ref[0] * corr + jnp.sum(p, axis=-1)
    num_ref[0] = num_ref[0] * corr[..., None] + jnp.einsum("hgk,khd->hgd", p, v)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_partials_tiles(q4: jax.Array, k_tiles: jax.Array, v_tiles: jax.Array,
                           length_eff: jax.Array, *, interpret: bool):
    """Core pallas_call. q4: (B, KVH, G, D) f32 pre-scaled; k/v_tiles:
    (B, T, tile, KVH, D); length_eff: (B,) i32. Returns (m, num, den) with
    shapes (B, KVH, G), (B, KVH, G, D), (B, KVH, G), all f32."""
    B, KVH, G, D = q4.shape
    T, tile = k_tiles.shape[1], k_tiles.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # the lengths ride in SMEM: a (1, 1) VMEM block of a (B, 1) array
        # breaks Mosaic's (8, 128) block rule
        num_scalar_prefetch=1,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, KVH, G, D), lambda b, t, _: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, tile, KVH, D), lambda b, t, _: (b, t, 0, 0, 0)),
            pl.BlockSpec((1, 1, tile, KVH, D), lambda b, t, _: (b, t, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, G), lambda b, t, _: (b, 0, 0)),
            pl.BlockSpec((1, KVH, G, D), lambda b, t, _: (b, 0, 0, 0)),
            pl.BlockSpec((1, KVH, G), lambda b, t, _: (b, 0, 0)),
        ])
    m, num, den = pl.pallas_call(
        functools.partial(_flash_decode_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, G), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, G), jnp.float32),
        ],
        # batch rows are independent ("parallel"); the KV-tile axis carries
        # the online-softmax state in the revisited output blocks, so it
        # must stay sequential ("arbitrary") — checked by repro.analysis
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(length_eff, q4, k_tiles, v_tiles)
    return m, num, den


# ---------------------------------------------------------------------------
# Static-analysis declaration (repro.analysis): mirrors the launch above
# ---------------------------------------------------------------------------

@register_spec("flash_decode")
def kernel_spec(B: int, S: int, KVH: int, G: int, D: int,
                kv_tile: int | None = None, point: str = "") -> KernelSpec:
    """KernelSpec at one attention geometry. ``kv_tile`` is the page size on
    the paged path (a page is a tile); contiguous uses KV_TILE clamped to S.
    The tile lands on the lane axis of the in-kernel score matrix, so it is
    declared lane-critical: ps < 128 under-fills the VPU."""
    tile = min(kv_tile or KV_TILE, S)
    T = -(-S // tile)
    return KernelSpec(
        name="flash_decode", module=__name__, grid=(B, T),
        in_blocks=(
            # scalar-prefetched lengths: SMEM-resident for the whole launch
            BlockDecl("len", (B,), "int32", memory=SMEM,
                      index_map=lambda b, t: (0,)),
            BlockDecl("q", (1, KVH, G, D), "float32",
                      index_map=lambda b, t: (b, 0, 0, 0)),
            BlockDecl("k", (1, 1, tile, KVH, D), "float32",
                      index_map=lambda b, t: (b, t, 0, 0, 0)),
            BlockDecl("v", (1, 1, tile, KVH, D), "float32",
                      index_map=lambda b, t: (b, t, 0, 0, 0)),
        ),
        out_blocks=(
            BlockDecl("m", (1, KVH, G), "float32",
                      index_map=lambda b, t: (b, 0, 0)),
            BlockDecl("num", (1, KVH, G, D), "float32",
                      index_map=lambda b, t: (b, 0, 0, 0)),
            BlockDecl("den", (1, KVH, G), "float32",
                      index_map=lambda b, t: (b, 0, 0)),
        ),
        dimension_semantics=("parallel", "arbitrary"),
        kernel_fn=_flash_decode_kernel,
        critical_lanes=(("kv_tile", tile),),
        point=point or f"B={B} S={S} KVH={KVH} G={G} D={D} tile={tile}")


def _prep_q(q: jax.Array, KVH: int):
    B, H, D = q.shape
    G = H // KVH
    return q.reshape(B, KVH, G, D).astype(jnp.float32) * D ** -0.5


def _length_eff(length: jax.Array, shard_offset, s_valid: int) -> jax.Array:
    # fold the slice's global offset into the mask (pos + off < length) and
    # clamp to the slice's real width: tile padding lies at pos >= s_valid
    # and must never pass the mask, even when the global length extends past
    # this slice (a later shard holds those positions)
    le = (jnp.asarray(length, jnp.int32)
          - jnp.asarray(shard_offset, jnp.int32)).reshape(-1)
    return jnp.minimum(le, jnp.int32(s_valid))


def decode_partials(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    length: jax.Array, *, shard_offset: jax.Array | int = 0,
                    kv_tile: int | None = None,
                    interpret: bool | None = None):
    """Kernel drop-in for ``dist.flash_decode.decode_partials`` (contiguous).

    q: (B, H, D); k_cache/v_cache: (B, S_slice, KVH, D); length: (B,) global
    valid prefix; ``shard_offset``: global position of this slice's first
    slot. The slice is padded to a multiple of ``kv_tile`` (default
    ``KV_TILE``, clamped to the slice) and reshaped — row-major, so the
    reshape is free — into (B, T, kv_tile, KVH, D) tiles; padding lands past
    ``length`` and is masked. Returns (m, num, den) as the jnp reference.
    """
    if interpret is None:
        interpret = _interpret()
    B, S, KVH, D = k_cache.shape
    G = q.shape[1] // KVH
    if S == 0:                       # zero-width slice: the empty contract
        return (jnp.full((B, KVH, G), NEG_INF, jnp.float32),
                jnp.zeros((B, KVH, G, D), jnp.float32),
                jnp.zeros((B, KVH, G), jnp.float32))
    tile = min(kv_tile or KV_TILE, S)
    pad = (-S) % tile
    if pad:
        cfg = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, cfg)
        v_cache = jnp.pad(v_cache, cfg)
    T = (S + pad) // tile
    kt = k_cache.reshape(B, T, tile, KVH, D)
    vt = v_cache.reshape(B, T, tile, KVH, D)
    return _decode_partials_tiles(_prep_q(q, KVH), kt, vt,
                                  _length_eff(length, shard_offset, S),
                                  interpret=interpret)


def decode_partials_pages(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                          length: jax.Array, *,
                          interpret: bool | None = None):
    """Page-native partials: the kvpool slab layout is already tiled.

    q: (B, H, D); k_pages/v_pages: (B, P, ps, KVH, D) exactly as
    ``PagePool.gather_pages`` emits them — each page is one KV tile, so the
    pool never materializes the contiguous ``seq_capacity``-wide cache;
    length: (B,) valid prefix over the concatenated pages. Returns
    (m, num, den). On TPU, ``ps`` should be lane-aligned (>= 128) for full
    VPU utilization; interpret mode accepts any page size.
    """
    if interpret is None:
        interpret = _interpret()
    _, P, ps, KVH, _ = k_pages.shape
    return _decode_partials_tiles(_prep_q(q, KVH), k_pages, v_pages,
                                  _length_eff(length, 0, P * ps),
                                  interpret=interpret)


def combine_partials(m, num, den, dtype=jnp.float32) -> jax.Array:
    """Normalize accumulated partials to the attention output (B, H, D).

    All-empty rows have num == den == 0 and come out exactly 0."""
    B, KVH, G, D = num.shape
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(B, KVH * G, D).astype(dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 length: jax.Array, *, kv_tile: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """Single-device kernel decode attention over a contiguous cache;
    drop-in for ``models.attention.decode_attention``."""
    m, num, den = decode_partials(q, k_cache, v_cache, length,
                                  kv_tile=kv_tile, interpret=interpret)
    return combine_partials(m, num, den, dtype=q.dtype)
