"""``quant.collect_outliers`` against an oracle that shares none of its code:
numpy's ``flatnonzero``, cut to the capacity and filled (index ``n``,
value 0).

The kernel path and the reference both call ``collect_outliers``, so their
agreement says nothing about it; these tests do. Patterns cover an empty
channel, outliers at the edges of the tiling, a channel filled exactly and
past its capacity, and many loop trips (a small ``OUTLIER_CHUNK``).
"""
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant

# 1D flat tiles with a partial last tile; 2D and 3D last-axis rows that are
# not multiples of the flat tile; 3D rows too narrow for their own tiling
# (flat tiles again), and 2D rows wider than the small chunk (flat there)
SHAPES = [(5000,), (37, 300), (3, 40, 130), (7, 9, 50), (3, 9000)]
PATTERNS = ["none", "last_tile", "exactly_k", "over_k", "all", "one_per_tile"]


def _oracle(resid: np.ndarray, k: int):
    flat = resid.ravel()
    nz = np.flatnonzero(flat)
    idx = np.full(k, flat.size, np.int32)
    val = np.zeros(k, np.int32)
    m = min(k, nz.size)
    idx[:m] = nz[:m]
    val[:m] = flat[nz[:m]]
    return idx, val, nz.size


def _width(shape) -> int:
    """Width of the rows ``collect_outliers`` tiles this shape with."""
    return jax.eval_shape(quant._outlier_rows,
                          jax.ShapeDtypeStruct(shape, jnp.int32)).shape[-1]


def _values(rng, n):
    v = rng.integers(40_000, 2**30, n).astype(np.int32)
    return np.where(rng.random(n) < 0.5, -v, v)


def _case(pattern: str, shape, rng):
    """(residual, capacity K) of one pattern."""
    n = int(np.prod(shape))
    flat = np.zeros(n, np.int32)
    k = max(1, n // 256)
    if pattern == "last_tile":
        flat[n - 1] = -70_000
    elif pattern == "exactly_k":
        flat[np.sort(rng.choice(n, k, replace=False))] = _values(rng, k)
    elif pattern == "over_k":
        pos = rng.choice(n, 3 * k + 5, replace=False)
        flat[pos] = _values(rng, pos.size)
    elif pattern == "all":
        flat[:] = _values(rng, n)
    elif pattern == "one_per_tile":
        w = _width(shape)
        pos = np.arange(0, n, w)
        pos = np.minimum(pos + (pos // w) % w, n - 1)
        flat[pos] = _values(rng, pos.size)
        k = pos.size + 3          # the whole channel fits, with spare slots
    return flat.reshape(shape), k


def _collect(resid, k):
    return jax.jit(partial(quant.collect_outliers, outlier_capacity=k))(
        jnp.asarray(resid))


@pytest.mark.parametrize("chunk", [None, 512], ids=["chunk", "small_chunk"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("pattern", PATTERNS)
def test_collect_outliers_matches_flatnonzero(pattern, shape, chunk, monkeypatch):
    if chunk is not None:
        monkeypatch.setattr(quant, "OUTLIER_CHUNK", chunk)
    rng = np.random.default_rng(zlib.crc32(f"{pattern}{shape}".encode()))
    resid, k = _case(pattern, shape, rng)
    idx, val, n_over = _collect(resid, k)
    want_idx, want_val, want_n = _oracle(resid, k)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(np.asarray(val), want_val)
    assert int(n_over) == want_n
    assert idx.dtype == val.dtype == n_over.dtype == jnp.int32


@pytest.mark.parametrize("shape", [(5000,), (3, 40, 130)], ids=["1d", "3d"])
def test_paper_mode_counts_only(shape):
    resid, _ = _case("over_k", shape, np.random.default_rng(1))
    idx, val, n_over = _collect(resid, 0)
    assert idx.shape == val.shape == (0,)
    assert int(n_over) == np.count_nonzero(resid)


def test_vmapped_rows_equal_unbatched_calls(monkeypatch):
    """Under ``vmap`` the loop runs the batch's largest trip count; each row
    must still come out as its own call gives it."""
    monkeypatch.setattr(quant, "OUTLIER_CHUNK", 1024)
    rng = np.random.default_rng(7)
    shape, k = (6000,), 40
    rows = [np.zeros(shape, np.int32), _case("last_tile", shape, rng)[0],
            _case("over_k", shape, rng)[0], _case("all", shape, rng)[0],
            _case("one_per_tile", shape, rng)[0]]
    batch = jnp.asarray(np.stack(rows))
    got = jax.jit(jax.vmap(partial(quant.collect_outliers, outlier_capacity=k)))(batch)
    for b, r in enumerate(rows):
        one = _collect(r, k)
        want = _oracle(r, k)
        for g, o, w in zip(got, one, want):
            np.testing.assert_array_equal(np.asarray(g[b]), np.asarray(o))
            np.testing.assert_array_equal(np.asarray(g[b]), w)
