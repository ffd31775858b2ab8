"""Optimized dual-quantization (FZ-GPU §3.2), pure-JAX reference semantics.

The only lossy stage of the pipeline:

    q_i   = round(d_i * (1 / (2 * eb)))    # pre-quantization (error <= eb)
    delta = Lorenzo(q)                      # integer finite differences (exact)
    code  = sign_magnitude_u16(delta)       # MSB = sign, no radius shift,
                                            # no separate outlier stream

FZ-GPU's departures from cuSZ (all reproduced here):
  * no +radius shift of quantization codes,
  * no separate outlier handling path (saturating codes instead),
  * sign carried in the MSB of an unsigned 16-bit code rather than
    2's complement, so small +/- values have mostly-zero high bits.

Beyond-paper option (``exact_outliers``): a fixed-capacity side channel of
(flat index, int32 residual) pairs restores the strict error bound even when
|delta| > 32767 (saturation would otherwise propagate through the Lorenzo
integration at decompression). Default ON for framework integrations, OFF for
the paper-faithful benchmark mode.

The functions here are the *oracles* for kernels/lorenzo_quant.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs

from . import encode as _enc

MAX_MAG = 0x7FFF  # largest representable |delta| in a sign-magnitude u16
SIGN_BIT = 0x8000


# ---------------------------------------------------------------------------
# Lorenzo predictor (on quantized integers -> integer deltas, exact)
# ---------------------------------------------------------------------------

def lorenzo_delta(q: jax.Array) -> jax.Array:
    """Forward Lorenzo transform: per-axis backward differences.

    For the Lorenzo predictor of any dimension, the prediction residual
    equals the composition of first differences along every axis
    (1D: v-W; 2D: v-N-W+NW; 3D: 7-point), with zero boundary conditions.
    Exact over int32.
    """
    if q.ndim > 3:
        raise ValueError(f"Lorenzo supports 1-3D, got {q.ndim}D")
    d = q
    for ax in range(q.ndim):
        d = jnp.diff(d, axis=ax, prepend=jnp.zeros_like(jax.lax.slice_in_dim(d, 0, 1, axis=ax)))
    return d


def lorenzo_inverse(delta: jax.Array) -> jax.Array:
    """Inverse Lorenzo transform: per-axis prefix sums (exact over int32)."""
    if delta.ndim > 3:
        raise ValueError(f"Lorenzo supports 1-3D, got {delta.ndim}D")
    q = delta
    for ax in range(delta.ndim):
        q = jnp.cumsum(q, axis=ax, dtype=delta.dtype)
    return q


# ---------------------------------------------------------------------------
# Integer delta <-> u16 code
# ---------------------------------------------------------------------------

def to_codes(delta: jax.Array, *, code_mode: str = "sign_mag"):
    """int32 delta -> (u16 code, overflow mask, int32 residual).

    ``sign_mag``  (paper-faithful): code = |d| & 0x7FFF | (d<0)<<15, saturating.
    ``zigzag``    (beyond-paper ablation): code = zigzag(d) saturated to u16;
                  maps the sign into the LSB which empirically yields denser
                  zero bit-planes after bitshuffle.
    residual = delta - decode(code): nonzero only where overflow.
    """
    d = delta.astype(jnp.int32)
    if code_mode == "sign_mag":
        mag = jnp.abs(d)
        over = mag > MAX_MAG
        sat = jnp.minimum(mag, MAX_MAG)
        code = sat.astype(jnp.uint16) | jnp.where(d < 0, jnp.uint16(SIGN_BIT), jnp.uint16(0))
        rec = jnp.where(d < 0, -sat, sat)
    elif code_mode == "zigzag":
        z = (d << 1) ^ (d >> 31)  # zigzag: 0,-1,1,-2,2 -> 0,1,2,3,4
        over = z > 0xFFFF
        zs = jnp.minimum(z, 0xFFFF)
        code = zs.astype(jnp.uint16)
        rec = (zs >> 1) ^ -(zs & 1)
    else:
        raise ValueError(f"unknown code_mode {code_mode!r}")
    return code, over, d - rec


def from_codes(code: jax.Array, *, code_mode: str = "sign_mag") -> jax.Array:
    """u16 code -> int32 delta (saturated value; residuals re-added separately)."""
    c = code.astype(jnp.int32)
    if code_mode == "sign_mag":
        mag = c & MAX_MAG
        return jnp.where(c & SIGN_BIT, -mag, mag)
    elif code_mode == "zigzag":
        return (c >> 1) ^ -(c & 1)
    raise ValueError(f"unknown code_mode {code_mode!r}")


# ---------------------------------------------------------------------------
# Full dual-quantization forward / inverse
# ---------------------------------------------------------------------------

def inv_two_eb(eb: jax.Array) -> jax.Array:
    """The correctly rounded float32 ``1 / (2 * eb)``, in integer arithmetic.

    Every element is quantized by *multiplying* with this one value. An f32
    multiply rounds the same under XLA and in the Pallas kernel, but a
    divide need not: the TPU has no divide unit, each compiler expands its
    own, and XLA may compute a scalar on another unit than a vector. Long
    division of the mantissas gives the same bits everywhere. Needs
    ``2 * eb`` to be a normal float32 below 2**126.
    """
    y = 2.0 * jnp.asarray(eb, jnp.float32)          # exact
    bits = jax.lax.bitcast_convert_type(y, jnp.int32)
    e = (bits >> 23) & 0xFF
    m = (bits & 0x7FFFFF) | 0x800000                # y = m * 2**(e - 150)
    rem, q = jnp.ones_like(m), jnp.zeros_like(m)
    for _ in range(47):                             # q = 2**47 // m
        rem = rem * 2
        bit = (rem >= m).astype(jnp.int32)
        rem, q = rem - bit * m, q * 2 + bit
    # q is in [2**23, 2**24]; round to nearest (a tie would need m | 2**48,
    # i.e. an exact quotient). Then 1/y = q * 2**(103 - e).
    q = q + (2 * rem >= m).astype(jnp.int32)
    carry = q >> 24                                 # q == 2**24: a power of 2
    out = ((253 - e + carry) << 23) | jnp.where(carry == 1, 0, q - 0x800000)
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def snap_eb(eb: jax.Array) -> jax.Array:
    """The largest bound <= ``eb`` with 8 significant bits.

    Then the step ``2 * eb`` has 8 significant bits too, so every
    reconstruction ``q * 2eb`` with ``|q| < 2**16`` is exact in float32 and
    :func:`quantize_scaled` can hold ``|x - q * 2eb| <= eb`` with no rounding
    allowance. It costs under 2**-7 of the step (< 0.012 bits per value).
    """
    bits = jax.lax.bitcast_convert_type(jnp.asarray(eb, jnp.float32), jnp.int32)
    return jax.lax.bitcast_convert_type(bits & ~0xFFFF, jnp.float32)


def step_scalars(eb: jax.Array) -> jax.Array:
    """float32 ``[inv, two_eb, tol]`` that :func:`quantize_scaled` takes:
    ``inv_two_eb(eb)``, ``2 * eb`` and ``eb``. The bound must be snapped
    (:func:`snap_eb`); ``fz`` snaps it where a container is built."""
    eb = jnp.asarray(eb, jnp.float32)
    return jnp.stack([inv_two_eb(eb), 2.0 * eb, eb])


def quantize_scaled(x: jax.Array, inv, two_eb, tol) -> jax.Array:
    """float32 ``x`` -> int32 ``q``, the nearest multiple of the step.

    ``q = rint(x * inv)``, then moved by one where the product's rounding
    picked the wrong neighbour: where the reconstruction ``q * two_eb``
    (what the decoder computes) lies further than ``tol`` from ``x``. The
    fix runs only where that reconstruction is exact (a snapped bound and
    ``|q| < 2**16``), so a compiler's choice to fuse the multiply and the
    subtract cannot change it; there ``|x - q * 2eb| <= eb`` holds exactly.
    Shared by the reference and the Pallas kernels, in the same float32
    operations, so both give the same bits.
    """
    qf = jnp.rint(x * inv)
    d = x - qf * two_eb
    exact = jnp.abs(qf) < 2.0 ** 16
    step = (jnp.where(exact & (d > tol), 1, 0)
            - jnp.where(exact & (d < -tol), 1, 0))
    return qf.astype(jnp.int32) + step


def prequantize(data: jax.Array, eb: jax.Array) -> jax.Array:
    """Pre-quantization of the reference: :func:`quantize_scaled` of the
    data in float32."""
    inv, two_eb, tol = step_scalars(eb)
    return quantize_scaled(data.astype(jnp.float32), inv, two_eb, tol)


@partial(jax.jit, static_argnames=("code_mode", "outlier_capacity"))
def dual_quantize(data: jax.Array, eb: jax.Array, *, code_mode: str = "sign_mag",
                  outlier_capacity: int = 0):
    """float data -> (u16 codes, outlier_idx, outlier_val, n_outliers).

    ``outlier_capacity`` == 0 reproduces the paper exactly (saturate & forget).
    With capacity K > 0, up to K overflowing deltas get exact int32 residuals
    recorded against their flat index (beyond-paper strict-error-bound mode).

    Preconditions (shared with SZ-family quantizers operating in float32):
      * ``eb`` is snapped (:func:`snap_eb`), as :func:`quantize_scaled`
        needs;
      * codes fit int32: ``max|d| / (2*eb) < 2**31`` (else q wraps; no outlier
        channel can repair that);
      * strict error bound additionally needs ``range/(2*eb) < ~2**21`` so the
        f32 multiply/rint/multiply round-trip stays within 1 q-unit. The paper's
        own evaluation range (rel eb 1e-2..1e-4, q <= 5000) sits far inside;
        beyond it the bound degrades gracefully to eb + O(ulp(data)).
    """
    q = prequantize(data, eb)
    delta = lorenzo_delta(q)
    codes, _, resid = to_codes(delta, code_mode=code_mode)
    return (codes, *collect_outliers(resid, outlier_capacity))


OUTLIER_TILE = 1024        # flat tile width where the rows do not serve
OUTLIER_CHUNK = 1 << 18    # residuals one trip of the compaction loop reads


def _outlier_rows(resid: jax.Array) -> jax.Array:
    """The residual as rows that tile its flat order: the array's own
    last-axis rows where they are 128 to ``OUTLIER_CHUNK`` wide (no
    relayout of a tiled array), else the flat residual zero-padded to rows
    of at most ``OUTLIER_TILE``, a multiple of 128."""
    if resid.ndim > 1 and 128 <= resid.shape[-1] <= OUTLIER_CHUNK:
        return resid
    flat = resid.ravel()
    width = min(OUTLIER_TILE, -(-flat.size // 128) * 128)
    return jnp.pad(flat, (0, (-flat.size) % width)).reshape(-1, width)


def collect_outliers(resid: jax.Array, outlier_capacity: int):
    """int32 residuals of :func:`to_codes` -> (outlier_idx i32[K],
    outlier_val i32[K], n_outliers i32[]).

    A residual is nonzero exactly where the code saturated. The first K of
    them, in flat order, keep their index and exact value; unused slots hold
    index ``n`` and value 0. K = 0 records only the count (paper mode).

    A tile-gated compaction, in time that grows with the outliers: one read
    of the residual counts each row's outliers (rows of
    :func:`_outlier_rows`), a scan over the row counts gives each row its
    first rank, and a loop gathers the rows that hold outliers with a rank
    below K, ``OUTLIER_CHUNK`` residuals a trip, and scatters their outliers
    to their ranks. With no outliers the loop makes no trip, and nothing
    touches the residual but the count.
    """
    with obs.span("fz.stage.collect_outliers"):
        n = resid.size
        if outlier_capacity == 0:
            empty = jnp.zeros((0,), jnp.int32)
            return empty, empty, jnp.sum(resid != 0, dtype=jnp.int32)
        k = outlier_capacity
        rows = _outlier_rows(resid)
        lead, width = rows.shape[:-1], rows.shape[-1]
        count = jnp.sum(rows != 0, axis=-1, dtype=jnp.int32).reshape(-1)
        n_rows = count.size
        first = _enc.exclusive_cumsum(count)
        live = ((count > 0) & (first < k)).astype(jnp.int32)
        live_end = _enc.exclusive_cumsum(live) + live
        n_live = jnp.sum(live)
        group = max(1, min(n_rows, OUTLIER_CHUNK // width))
        lane = jnp.arange(width, dtype=jnp.int32)
        spill = k + jnp.arange(group * width, dtype=jnp.int32).reshape(group, width)

        def trip(carry):
            t, idx, val = carry
            with jax.named_scope("outlier_chunk"):
                j = t * group + jnp.arange(group, dtype=jnp.int32)
                row = jnp.searchsorted(live_end, j, side="right",
                                       method="scan_unrolled").astype(jnp.int32)
                row = jnp.minimum(row, n_rows - 1)
                r = rows[jnp.unravel_index(row, lead)]
                hit = (r != 0) & (j < n_live)[:, None]
                h = hit.astype(jnp.int32)
                rank = first[row][:, None] + jnp.cumsum(h, axis=1) - h
                # ranks past K and misses go to distinct slots past the end
                dst = jnp.where(hit & (rank < k), rank, spill)
                idx = idx.at[dst].set(row[:, None] * width + lane, mode="drop",
                                      unique_indices=True)
                val = val.at[dst].set(r, mode="drop", unique_indices=True)
                return t + 1, idx, val

        _, idx, val = jax.lax.while_loop(
            lambda c: c[0] * group < n_live, trip,
            (jnp.int32(0), jnp.full((k,), n, jnp.int32), jnp.zeros((k,), jnp.int32)))
        return idx, val, jnp.sum(count)


@partial(jax.jit, static_argnames=("shape", "code_mode"))
def dual_dequantize(codes: jax.Array, eb: jax.Array, shape: tuple[int, ...], *,
                    code_mode: str = "sign_mag",
                    outlier_idx: jax.Array | None = None,
                    outlier_val: jax.Array | None = None) -> jax.Array:
    """u16 codes (+ optional outlier residuals) -> reconstructed float32."""
    delta = from_codes(codes, code_mode=code_mode).ravel()
    if outlier_idx is not None and outlier_idx.size:
        delta = delta.at[jnp.minimum(outlier_idx, delta.size - 1)].add(
            jnp.where(outlier_idx < delta.size, outlier_val, 0), mode="drop")
    q = lorenzo_inverse(delta.reshape(shape))
    return q.astype(jnp.float32) * (2.0 * eb)
