"""The plain reference that decides ``correct``, and its control.

What an error-bounded compressor promises its user, as the FZ compressor
states it (``fz.resolve_eb``, ``quant.snap_eb``, ``quant.quantize_scaled``):

- the bound ``eb_abs``: in ``rel`` mode ``eb * max(max - min, max |x|)``,
  in ``abs`` mode ``eb``, in float32 and floored at 1e-30, then cut down to
  8 significant bits;
- the reconstruction: every value is ``q * 2 * eb_abs``, ``q`` the integer
  nearest to ``x / (2 * eb_abs)`` (at an exact tie, either neighbour), so
  ``|x - x'| <= eb_abs`` everywhere.

The reference works that out again, on the host in float64 numpy, from
the source field alone: it imports nothing of the program and takes
nothing the program made.

Numbers compared, each against its limit (``LIMITS``):

- ``mismatch``: values of the reconstruction that are not the reference's;
- ``max_err_over_eb``: the largest ``|x - x'|`` over the reference's
  ``eb_abs``, which the bound holds at 1;
- ``truncated_mismatch``: values that change when the container is decoded
  with everything past its used bytes cleared, so the ratio counts every
  byte the reconstruction needs.

The control is the same reference computed in bfloat16, the precision
below the float32 the configurations state.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

LIMITS = {"mismatch": 0, "max_err_over_eb": 1.0, "truncated_mismatch": 0}
BLOCK = 1 << 23            # elements per block of the float64 pass


def bound(x: np.ndarray, eb: float, eb_mode: str) -> np.float32:
    """The container's ``eb_abs`` as the compressor states it."""
    if eb_mode == "abs":
        e = np.float32(eb)
    elif eb_mode == "rel":
        lo, hi = np.float32(x.min()), np.float32(x.max())
        scale = max(np.float32(hi - lo), np.float32(max(abs(lo), abs(hi))))
        e = np.float32(np.float32(eb) * scale)
    else:
        raise ValueError(f"unknown eb_mode {eb_mode!r}")
    e = max(e, np.float32(1e-30))
    return (np.asarray(e).view(np.int32) & np.int32(~0xFFFF)).view(np.float32)[()]


def compare(x: np.ndarray, rec: np.ndarray, eb: float, eb_mode: str) -> dict:
    """``mismatch`` and ``max_err_over_eb`` of one reconstruction.

    A value is the reference's when it lies on the grid of ``2 eb_abs`` and
    within ``eb_abs`` of the source: the nearest grid point, or at an exact
    tie either one. Both tests are exact in float64: ``x - x'`` of two
    float32 values is, and ``x' / (2 eb_abs)`` is an integer exactly when
    ``x'`` is on the grid (an 8-bit step leaves 29 spare bits).
    """
    if rec.shape != x.shape or rec.dtype != np.float32:
        return {"mismatch": x.size, "max_err_over_eb": float("inf")}
    eb_abs = float(bound(x, eb, eb_mode))
    two = 2.0 * eb_abs
    xs, rs = x.reshape(-1), rec.reshape(-1)
    bad, err = 0, 0.0
    for s in range(0, xs.size, BLOCK):
        r = rs[s:s + BLOCK].astype(np.float64)
        d = np.abs(xs[s:s + BLOCK] - r)
        q = r / two
        bad += int(np.count_nonzero((d > eb_abs) | (q != np.rint(q))))
        err = max(err, float(d.max()))
    return {"mismatch": bad, "max_err_over_eb": err / eb_abs}


def control(x: np.ndarray, eb: float, eb_mode: str) -> np.ndarray:
    """The reference's reconstruction computed in bfloat16."""
    bf = ml_dtypes.bfloat16
    two = np.asarray(2.0 * float(bound(x, eb, eb_mode)), bf)
    xb = x.astype(bf)
    return (np.rint(xb / two).astype(bf) * two).astype(np.float32)


def verdict(numbers: dict) -> bool:
    """True when every number compared is within its limit."""
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
