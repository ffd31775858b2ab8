"""Single-launch fused FZ compress megakernel (paper §3.5 taken to its limit).

One ``pallas_call`` runs the ENTIRE compression pipeline — pre-quantization +
Lorenzo (with the 1-row halo BlockSpec from kernels/lorenzo_quant) +
bitshuffle + zero-block flagging + phase-2 compaction — so the u16 code
stream and the shuffled word stream live and die in VMEM. The staged kernel
path (lorenzo_quant, then bitshuffle_flag, then an XLA ``cumsum``/``nonzero``/
``take`` epilogue) round-trips both streams through HBM (~4n extra bytes on
an n-byte input); here HBM sees only the float input and the container
outputs.

Grid-band reconciliation: Lorenzo wants leading-axis bands (all trailing-axis
differences band-internal, one halo row/plane for the leading axis) while the
shuffle wants whole TILE=4096-code tiles. A band of ``band * trailing`` codes
is generally tile-misaligned, so the kernel exploits the TPU grid's
*sequential* execution: a VMEM scratch buffer carries the < TILE leftover
codes of each step into the next (right-aligned, so every concatenation point
is static), and only whole tiles are shuffled per step. Steps beyond the last
band (when the zero-padded stream outruns ``bands * band * trailing``) reuse
the clamped final band and mask everything to the zero pad.

Phase-2 compaction (the decoupled-lookback analogue): the running payload
offset rides in SMEM scratch across sequential grid steps; each step computes
its blocks' global offsets as ``smem_offset + local exclusive cumsum`` and
scatters surviving 16-byte blocks straight into the word-major payload
output (column ``capacity`` is a write-off trash slot for beyond-capacity blocks, sliced off
by the wrapper). ``jnp.nonzero`` and the full-stream materialization are gone.

TPU notes: the sequential carry requires ``dimension_semantics=("arbitrary",)``
(set below; interpret mode ignores it). The in-kernel scatter/gather on the
payload ref and the element-granular dynamic slice of the stream buffer are
interpreter-validated on CPU; Mosaic lowering of those two ops (plus the
VMEM residency of a capacity-sized payload) is the open hillclimb item
tracked in ROADMAP.md — production shapes (pages, gradient leaves) are
lane-aligned, the adversarial odd shapes of the property suite are
interpret-only either way.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.kernelspec import (BlockDecl, KernelSpec, ScratchDecl,
                                       register_spec)
from repro.core import quant as _quant
from . import bitshuffle_flag as _bsf
from . import lorenzo_quant as _lq

TILE = _bsf.TILE                                  # 4096 codes per shuffle tile
GROUP = _bsf.GROUP                                # 16
GROUPS_PER_TILE = _bsf.GROUPS_PER_TILE            # 256
BLOCK_WORDS = _bsf.BLOCK_WORDS                    # 8 u16 words per zero block
BLOCKS_PER_TILE = _bsf.BLOCKS_PER_TILE            # 512
FLAG_WORDS_PER_TILE = BLOCKS_PER_TILE // 32       # 16 packed u32 per tile
ROW_1D = _lq.ROW_1D                               # flattened-1D row width
ENCODE_TILES = 8                                  # tiles per fused_shuffle_encode step


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Static geometry reconciling Lorenzo bands with TILE-aligned code tiles.

    Shared by the compress and decompress megakernels so both walk the code
    stream in exactly the same band order (and therefore agree on where every
    band's codes sit in the tiled stream).
    """
    shape: tuple                  # original array shape
    kern_nd: int                  # dims the kernel sees (1 == rows x ROW_1D)
    lead: int                     # leading-axis length of the kernel view
    trailing: tuple               # trailing axes of the kernel view
    band: int                     # leading rows/planes per grid step
    bands: int                    # ceil(lead / band)
    m: int                        # codes produced per grid step
    n: int                        # real elements
    padded_n: int                 # code-stream length (TILE multiple)
    total_tiles: int              # padded_n // TILE

    @property
    def wmax_compress(self) -> int:
        """Most whole tiles one compress step can complete (carry < TILE)."""
        return (TILE - 1 + self.m) // TILE

    @property
    def wmax_decode(self) -> int:
        """Most whole tiles one decode step may need to open."""
        return (self.m + TILE - 1) // TILE

    @property
    def flag_words(self) -> int:
        return self.total_tiles * FLAG_WORDS_PER_TILE


def _fused_band(trailing_elems: int, *, itemsize: int = 4) -> int:
    """Band sizing for the fused kernels: at least ~2 tiles of codes per step
    (so tiny trailing axes don't degenerate into thousands of carry-only
    steps) but still within the per-band VMEM budget for wide planes.

    ``itemsize`` is the band input's element size, mirroring
    ``lorenzo_quant.band_for``'s dtype awareness. The fused wrappers cast
    to f32 before the launch today (the StreamPlan must agree between the
    compress and decompress megakernels, and decode's band output is always
    f32), so they plan at the default itemsize=4; the parameter keeps the
    budget math honest for the analyzer and for a future native-bf16 plan.
    """
    budget_rows = max(1, _lq.VMEM_BAND_BUDGET // (itemsize * trailing_elems))
    want = max(_lq.MAX_BAND, -(-2 * TILE // trailing_elems))
    return max(1, min(budget_rows, want))


def plan_stream(shape: tuple[int, ...]) -> StreamPlan:
    ndim = len(shape)
    if not 1 <= ndim <= 3:
        raise ValueError(f"fused FZ kernels support 1-3D, got {ndim}D")
    n = 1
    for s in shape:
        n *= s
    if ndim == 1:
        lead, trailing, kern_nd = -(-n // ROW_1D), (ROW_1D,), 1
    else:
        lead, trailing, kern_nd = shape[0], tuple(shape[1:]), ndim
    t_elems = 1
    for s in trailing:
        t_elems *= s
    band = _fused_band(t_elems)
    bands = -(-lead // band)
    padded_n = -(-n // TILE) * TILE
    return StreamPlan(shape=tuple(shape), kern_nd=kern_nd, lead=lead,
                      trailing=trailing, band=band, bands=bands,
                      m=band * t_elems, n=n, padded_n=padded_n,
                      total_tiles=padded_n // TILE)


def _pad_to_kernel_view(data: jax.Array, p: StreamPlan) -> jax.Array:
    """float32 (1-3)D array -> padded (bands*band, *trailing) kernel view."""
    x = data.astype(jnp.float32)
    if p.kern_nd == 1:
        x = jnp.pad(x.reshape(-1), (0, p.lead * ROW_1D - p.n)).reshape(p.lead, ROW_1D)
    pad_lead = p.bands * p.band - p.lead
    return jnp.pad(x, [(0, pad_lead)] + [(0, 0)] * (x.ndim - 1))


def _shuffle_tiles(proc: jax.Array, wmax: int):
    """(wmax*TILE,) u16 codes -> (shuffled (wmax, TILE), blocks, flags)."""
    groups = proc.reshape(wmax * GROUPS_PER_TILE, GROUP)
    t = _bsf.transpose16_inkernel(groups).reshape(wmax, GROUPS_PER_TILE, GROUP)
    shuffled = jnp.swapaxes(t, 1, 2).reshape(wmax, TILE)
    blocks = shuffled.reshape(wmax * BLOCKS_PER_TILE, BLOCK_WORDS)
    flags = jnp.any(blocks != 0, axis=-1)
    return blocks, flags


def _pack_flag_words(fv: jax.Array, nb: int) -> jax.Array:
    """(nb,) bool flags -> (nb//32,) packed u32 words (LSB-first)."""
    bits = fv.reshape(nb // 32, 32).astype(jnp.uint32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (nb // 32, 32), 1)
    return jnp.sum(bits << shifts, axis=1, dtype=jnp.uint32)


def _compact_into_payload(payload_ref, blocks, fv, base_off, capacity: int):
    """Scatter surviving blocks at ``base_off + local exclusive cumsum``.

    The payload ref is word-major (8, capacity + 1), as the container's;
    column ``capacity`` is the trash slot: non-surviving and beyond-capacity
    blocks land there (reference semantics drop them). Returns this step's
    survivor count.
    """
    nb = fv.shape[0]
    fv_i = fv.astype(jnp.int32).reshape(1, nb)
    excl = (jnp.cumsum(fv_i, axis=1) - fv_i).reshape(nb)
    off = base_off + excl
    idx = jnp.where(fv & (off < capacity), off, capacity)
    payload_ref[:, idx] = blocks.T
    return jnp.sum(fv_i, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Full megakernel: float data -> (bitflags, payload, nnz) in one launch
# ---------------------------------------------------------------------------

def _make_compress_kernel(p: StreamPlan, capacity: int, code_mode: str):
    m, wmax = p.m, p.wmax_compress
    nb = wmax * BLOCKS_PER_TILE

    def kernel(x_ref, halo_ref, step_ref, bitflags_ref, payload_ref, nnz_ref,
               carry_ref, sm_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            sm_ref[0] = 0                        # carry length (codes)
            sm_ref[1] = 0                        # running payload offset
            sm_ref[2] = 0                        # tiles emitted so far
            carry_ref[...] = jnp.zeros((1, TILE), jnp.uint16)
            payload_ref[...] = jnp.zeros((BLOCK_WORDS, capacity + 1), jnp.uint16)
            nnz_ref[0, 0] = 0

        codes = _lq.band_codes(x_ref[...], halo_ref[...],
                               tuple(step_ref[0, j] for j in range(3)),
                               ndim=p.kern_nd, code_mode=code_mode,
                               keep_halo=jnp.minimum(i, 1))
        flat = codes.astype(jnp.uint16).reshape(1, m)
        # zero everything past the real data: the stream then matches the
        # reference's zero-padded flat code stream exactly, including the
        # grid's flush steps past the last band (whose clamped input band is
        # entirely masked here)
        pos = i * m + jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
        flat = jnp.where(pos < p.n, flat, jnp.uint16(0))

        carry_len = sm_ref[0]
        # stream buffer: [0, TILE) carry (right-aligned, valid suffix is the
        # last carry_len codes), [TILE, TILE+m) this band's codes,
        # [TILE+m, 2*TILE+m) zero slack so the wmax-tile slice below is safe
        buf = jnp.concatenate(
            [carry_ref[...], flat, jnp.zeros((1, TILE), jnp.uint16)], axis=1)
        w = (carry_len + m) // TILE              # whole tiles ready this step
        proc = jax.lax.dynamic_slice(
            buf, (0, TILE - carry_len), (1, wmax * TILE)).reshape(-1)
        blocks, flags = _shuffle_tiles(proc, wmax)

        tiles_done = sm_ref[2]
        tile_of = jax.lax.broadcasted_iota(
            jnp.int32, (wmax, BLOCKS_PER_TILE), 0).reshape(nb)
        fv = flags & (tile_of < w) & (tiles_done + tile_of < p.total_tiles)

        step_nnz = _compact_into_payload(payload_ref, blocks, fv, sm_ref[1],
                                         capacity)
        # invalid-tail words are overwritten by the next step (or land in the
        # wrapper-sliced pad region), so the store needs no per-tile predicate
        bitflags_ref[0, pl.ds(tiles_done * FLAG_WORDS_PER_TILE,
                              wmax * FLAG_WORDS_PER_TILE)] = \
            _pack_flag_words(fv, nb)

        nnz_ref[0, 0] += step_nnz
        sm_ref[1] += step_nnz
        sm_ref[2] = tiles_done + w
        sm_ref[0] = carry_len + m - w * TILE
        # the last TILE codes of the valid stream (ending at buf[TILE+m))
        # become the next step's right-aligned carry — a static slice
        carry_ref[...] = buf[:, m:m + TILE]

    return kernel


@functools.partial(jax.jit, static_argnames=("code_mode", "capacity", "interpret"))
def fused_compress(data: jax.Array, eb: jax.Array, *, capacity: int,
                   code_mode: str = "sign_mag", interpret: bool = False):
    """float (1-3)D -> (bitflags u32[W], payload u16[8, capacity], nnz i32[]).

    Bit-identical to ``enc.encode(shuffle.bitshuffle(pad(quantize(data))))``
    with the code stream never leaving VMEM.
    """
    p = plan_stream(data.shape)
    x = _pad_to_kernel_view(data, p)
    # flush steps keep the grid going until the zero-padded stream completes
    steps = max(p.bands, -(-p.padded_n // p.m))
    wmax = p.wmax_compress
    fw_pad = p.flag_words + wmax * FLAG_WORDS_PER_TILE

    band_block = (p.band, *p.trailing)
    zeros_trail = (0,) * len(p.trailing)

    def band_index(i):
        return (jnp.minimum(i, p.bands - 1), *zeros_trail)

    def halo_index(i):
        return (jnp.maximum(jnp.minimum(i, p.bands - 1) * p.band - 1, 0),
                *zeros_trail)

    step = jnp.reshape(_quant.step_scalars(eb), (1, 3))
    bitflags, payload, nnz = pl.pallas_call(
        _make_compress_kernel(p, capacity, code_mode),
        grid=(steps,),
        in_specs=[pl.BlockSpec(band_block, band_index),
                  pl.BlockSpec((1, *p.trailing), halo_index),
                  pl.BlockSpec((1, 3), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, fw_pad), lambda i: (0, 0)),
                   pl.BlockSpec((BLOCK_WORDS, capacity + 1), lambda i: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, fw_pad), jnp.uint32),
                   jax.ShapeDtypeStruct((BLOCK_WORDS, capacity + 1), jnp.uint16),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, TILE), jnp.uint16),
                        pltpu.SMEM((4,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, x, step)
    return bitflags[0, :p.flag_words], payload[:, :capacity], nnz[0, 0]


# ---------------------------------------------------------------------------
# Codes-input megakernel: fused shuffle + flag + compaction (the outlier
# route — reference quantization already materialized the codes)
# ---------------------------------------------------------------------------

def _make_encode_kernel(capacity: int, tiles_per_step: int):
    nb = tiles_per_step * BLOCKS_PER_TILE

    def kernel(codes_ref, bitflags_ref, payload_ref, nnz_ref, sm_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            sm_ref[0] = 0
            payload_ref[...] = jnp.zeros((BLOCK_WORDS, capacity + 1), jnp.uint16)
            nnz_ref[0, 0] = 0

        blocks, flags = _shuffle_tiles(codes_ref[...].reshape(-1),
                                       tiles_per_step)
        # grid-padding tiles are all-zero codes -> never flagged, so no
        # tile-validity mask is needed on this aligned path
        step_nnz = _compact_into_payload(payload_ref, blocks, flags,
                                         sm_ref[0], capacity)
        bitflags_ref[...] = _pack_flag_words(
            flags, nb).reshape(1, tiles_per_step * FLAG_WORDS_PER_TILE)
        nnz_ref[0, 0] += step_nnz
        sm_ref[0] += step_nnz

    return kernel


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def fused_shuffle_encode(codes_flat: jax.Array, *, capacity: int,
                         interpret: bool = False):
    """(k*TILE,) u16 codes -> (bitflags, payload, nnz), compaction in-kernel.

    The kernelized phase 2 on its own: replaces the staged path's XLA
    ``cumsum`` + ``nonzero`` + ``take`` epilogue (and its full shuffled-stream
    HBM materialization) for callers that already hold the code stream.
    """
    if codes_flat.size % TILE:
        raise ValueError(f"size {codes_flat.size} not a multiple of TILE={TILE}")
    n_tiles = codes_flat.size // TILE
    tps = ENCODE_TILES
    padded = -(-n_tiles // tps) * tps
    x = jnp.pad(codes_flat.reshape(n_tiles, TILE), ((0, padded - n_tiles), (0, 0)))
    flag_words = n_tiles * FLAG_WORDS_PER_TILE
    bitflags, payload, nnz = pl.pallas_call(
        _make_encode_kernel(capacity, tps),
        grid=(padded // tps,),
        in_specs=[pl.BlockSpec((tps, TILE), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, tps * FLAG_WORDS_PER_TILE), lambda i: (0, i)),
                   pl.BlockSpec((BLOCK_WORDS, capacity + 1), lambda i: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(
                       (1, padded * FLAG_WORDS_PER_TILE), jnp.uint32),
                   jax.ShapeDtypeStruct((BLOCK_WORDS, capacity + 1), jnp.uint16),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x)
    return bitflags[0, :flag_words], payload[:, :capacity], nnz[0, 0]


# ---------------------------------------------------------------------------
# Static-analysis declarations (repro.analysis): mirror the launches above
# ---------------------------------------------------------------------------

def _capacity_for(n: int, capacity_frac: float) -> int:
    """FZConfig.payload_capacity restated on this module's constants."""
    n_blocks = (-(-n // TILE) * TILE) // BLOCK_WORDS
    return max(1, int(n_blocks * capacity_frac))


@register_spec("fused_compress")
def kernel_spec(shape: tuple[int, ...], capacity_frac: float = 1.0,
                dtype: str = "float32") -> KernelSpec:
    """KernelSpec for ``fused_compress``. ``dtype`` is the *source* dtype;
    the wrapper casts to f32 before launch (the StreamPlan must agree with
    the decode megakernel), so the modeled input block is always f32."""
    p = plan_stream(tuple(shape))
    capacity = _capacity_for(p.n, capacity_frac)
    steps = max(p.bands, -(-p.padded_n // p.m))
    wmax = p.wmax_compress
    fw_pad = p.flag_words + wmax * FLAG_WORDS_PER_TILE
    zeros_trail = (0,) * len(p.trailing)
    clamp = p.bands - 1
    return KernelSpec(
        name="fused_compress", module=__name__, grid=(steps,),
        in_blocks=(
            BlockDecl("x", (p.band, *p.trailing), "float32",
                      index_map=lambda i: (min(i, clamp), *zeros_trail)),
            BlockDecl("halo", (1, *p.trailing), "float32",
                      index_map=lambda i: (max(min(i, clamp) * p.band - 1, 0),
                                           *zeros_trail)),
            BlockDecl("step", (1, 3), "float32", index_map=lambda i: (0, 0)),
        ),
        out_blocks=(
            BlockDecl("bitflags", (1, fw_pad), "uint32",
                      index_map=lambda i: (0, 0)),
            BlockDecl("payload", (BLOCK_WORDS, capacity + 1), "uint16",
                      index_map=lambda i: (0, 0)),
            BlockDecl("nnz", (1, 1), "int32", index_map=lambda i: (0, 0)),
        ),
        scratch=(ScratchDecl("carry", (1, TILE), "uint16", "vmem"),
                 ScratchDecl("sm", (4,), "int32", "smem")),
        dimension_semantics=("arbitrary",),
        kernel_fn=_make_compress_kernel(p, capacity, "sign_mag"),
        point=(f"shape={tuple(shape)} src={dtype} "
               f"capacity_frac={capacity_frac} capacity={capacity}"))


@register_spec("fused_shuffle_encode")
def _encode_spec(n_tiles: int, capacity_frac: float = 1.0) -> KernelSpec:
    tps = ENCODE_TILES
    padded = -(-max(n_tiles, 1) // tps) * tps
    capacity = _capacity_for(n_tiles * TILE, capacity_frac)
    return KernelSpec(
        name="fused_shuffle_encode", module=__name__, grid=(padded // tps,),
        in_blocks=(BlockDecl("codes", (tps, TILE), "uint16",
                             index_map=lambda i: (i, 0)),),
        out_blocks=(
            BlockDecl("bitflags", (1, tps * FLAG_WORDS_PER_TILE), "uint32",
                      index_map=lambda i: (0, i)),
            BlockDecl("payload", (BLOCK_WORDS, capacity + 1), "uint16",
                      index_map=lambda i: (0, 0)),
            BlockDecl("nnz", (1, 1), "int32", index_map=lambda i: (0, 0)),
        ),
        scratch=(ScratchDecl("sm", (1,), "int32", "smem"),),
        dimension_semantics=("arbitrary",),
        kernel_fn=_make_encode_kernel(capacity, tps),
        point=f"n_tiles={n_tiles} capacity_frac={capacity_frac}")
