"""jit'd public wrappers around the Pallas kernels.

Interpret mode: on non-TPU backends the
kernels execute through the Pallas interpreter, which runs the kernel body
in Python/XLA for bit-exact validation against ref.py. On TPU the same
pallas_call lowers to Mosaic. ``backend_interpret()`` is the one shared
backend check — benchmarks and callers outside this package route through it
instead of hardcoding ``interpret=True``.

Two kernel flavors, selected by ``FZConfig.kernel_mode`` (see core/fz.py):

  * ``"fused"``: single-launch megakernels — the whole compress pipeline in
    one pallas_call (fused_compress.py) and the whole decompress pipeline in
    another (fused_decode.py); the code stream never touches HBM. Mosaic
    does not compile them for the v5e yet, so a TPU never routes here
    (``tune.dispatch.TPU_FUSED_MAX_ELEMS``).
  * ``"staged"``: per-stage kernels (lorenzo_quant, then bitshuffle_flag
    with an XLA phase-2 epilogue; bitunshuffle on the way back) — the path
    a TPU runs at every size.

Signature compatibility: the staged wrappers expose the same interfaces as
the reference stages in repro.core so FZConfig swaps them in transparently
(see core/fz.py:_stages); the fused wrappers produce whole containers' worth
of fields per call.

The staged wrappers open no span: ``core/fz.py`` opens the
``fz.stage.<name>`` scope around each call, the same for the kernels and
the reference. The fused wrappers run under
``obs.span("fz.stage.fused_compress"/"fused_decompress", backend=...)``.
These execute while jax is tracing the enclosing fz jit, so they record
once-per-compilation ``jit-trace`` events (nested, by timestamp, inside the
eager ``fz.compress``/``fz.decompress`` wrapper span that triggered the
compile) and the ``named_scope`` lands the stage name in XLA op metadata —
no runtime footprint in the compiled program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import encode as _enc
from repro.core import quant as _quant
from repro.core import shuffle as _shuffle
from . import bitshuffle_flag as _bsf
from . import fused_compress as _fc
from . import fused_decode as _fd
from . import lorenzo_quant as _lq

TILE = _bsf.TILE


def backend_interpret() -> bool:
    """True when the Pallas kernels must run under the interpreter (non-TPU).

    The single source of truth for backend routing: kernels lower to Mosaic
    exactly when the default backend is a TPU, and benchmarks that want "the
    real lowering where available" ask here instead of pinning interpret=True.
    """
    return jax.default_backend() != "tpu"


def backend_label() -> str:
    """Span/metric label for where the kernels execute."""
    return "interpret" if backend_interpret() else "tpu"


# ---------------------------------------------------------------------------
# Staged kernel path ("kernel_mode=staged"): per-stage launches, XLA phase 2
# ---------------------------------------------------------------------------

def lorenzo_quantize(data: jax.Array, eb: jax.Array, *, code_mode: str = "sign_mag",
                     outlier_capacity: int = 0):
    """Kernel-path dual-quantization, same signature as
    ``core.quant.dual_quantize``.

    Paper mode (outlier_capacity == 0) saturates and forgets. Strict mode
    (outlier_capacity > 0) has the kernel also write the int32 residuals,
    which XLA compacts into the exact-outlier side channel.
    """
    if outlier_capacity > 0:
        codes, resid = _lq.lorenzo_quant(
            data, eb, code_mode=code_mode, with_residual=True,
            interpret=backend_interpret())
        return (codes, *_quant.collect_outliers(resid, outlier_capacity))
    codes = _lq.lorenzo_quant(data, eb, code_mode=code_mode,
                              interpret=backend_interpret())
    zero_i = jnp.zeros((0,), jnp.int32)
    return codes, zero_i, zero_i, jnp.int32(0)


@partial(jax.jit, static_argnames=("capacity",))
def bitshuffle_flag_encode(codes_flat: jax.Array, *, capacity: int):
    """Fused kernel (shuffle + phase-1 flags) + XLA phase-2 (scan + gather).

    Matches repro.core.encode.encode(bitshuffle(codes_flat), capacity).
    """
    if codes_flat.size % TILE:
        raise ValueError(f"size {codes_flat.size} not a multiple of TILE={TILE}")
    tiles = codes_flat.reshape(-1, TILE)
    shuffled, byteflags = _bsf.bitshuffle_flag(tiles, interpret=backend_interpret())
    flags = byteflags.reshape(-1).astype(bool)
    return _enc.compact_blocks(
        flags, shuffled.reshape(_enc.BLOCK_WORDS, -1), capacity=capacity)


@jax.jit
def bitshuffle(codes_flat: jax.Array) -> jax.Array:
    """Shuffle-only kernel path (flags discarded) for tests/benchmarks:
    flat codes -> flat shuffled words, as core.shuffle.bitshuffle."""
    shuffled, _ = _bsf.bitshuffle_flag(codes_flat.reshape(-1, TILE),
                                       interpret=backend_interpret())
    return shuffled.reshape(_enc.BLOCK_WORDS, -1).T.reshape(-1)


@jax.jit
def bitunshuffle(words: jax.Array) -> jax.Array:
    """Inverse transform kernel: word-major (8, n_blocks) shuffled words
    (``core.encode.decode_blocks``) -> flat codes."""
    tiles = words.reshape(_enc.BLOCK_WORDS, -1, _bsf.BLOCKS_PER_TILE)
    return _bsf.bitunshuffle_tiles(tiles, interpret=backend_interpret()).reshape(-1)


# ---------------------------------------------------------------------------
# Fused megakernel path ("kernel_mode=fused"): one launch per direction
# ---------------------------------------------------------------------------

def fused_compress_stages(data: jax.Array, eb: jax.Array, *,
                          code_mode: str, capacity: int,
                          outlier_capacity: int = 0):
    """One-launch compress: (bitflags, payload, nnz, oidx, oval, n_over).

    The exact residual side channel needs the unsaturated int32 deltas,
    which the megakernel never materializes (codes are born saturated in
    VMEM). With ``outlier_capacity > 0`` quantization therefore runs as the
    staged quantization kernel, residuals included, and the fused
    shuffle+flag+compaction megakernel takes its codes — still no
    shuffled-stream HBM round trip, and the strict error bound is preserved
    (pinned in tests/test_kernels.py).
    """
    with obs.span("fz.stage.fused_compress", backend=backend_label()):
        if outlier_capacity > 0:
            codes, oidx, oval, n_over = lorenzo_quantize(
                data, eb, code_mode=code_mode, outlier_capacity=outlier_capacity)
            flat = _shuffle.pad_to_tiles(codes.reshape(-1))
            bitflags, payload, nnz = _fc.fused_shuffle_encode(
                flat, capacity=capacity, interpret=backend_interpret())
            return bitflags, payload, nnz, oidx, oval, n_over
        bitflags, payload, nnz = _fc.fused_compress(
            data, eb, capacity=capacity, code_mode=code_mode,
            interpret=backend_interpret())
        zero_i = jnp.zeros((0,), jnp.int32)
        return bitflags, payload, nnz, zero_i, zero_i, jnp.int32(0)


def fused_decompress(bitflags: jax.Array, payload: jax.Array, eb: jax.Array, *,
                     shape: tuple[int, ...], code_mode: str,
                     outlier_idx: jax.Array | None = None,
                     outlier_val: jax.Array | None = None) -> jax.Array:
    """One-launch decompress mirroring :func:`fused_compress_stages`."""
    with obs.span("fz.stage.fused_decompress", backend=backend_label()):
        return _fd.fused_decompress(
            bitflags, payload, eb, shape=tuple(shape), code_mode=code_mode,
            outlier_idx=outlier_idx, outlier_val=outlier_val,
            interpret=backend_interpret())
