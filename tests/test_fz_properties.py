"""Property-based tests for the FZ pipeline invariants.

Two tiers share one set of checkers:
  * hypothesis-driven search when the wheel is available;
  * a seeded ``np.random`` parametrized fallback that always runs, so the
    round-trip / error-bound properties are exercised even in hermetic
    (no-network) environments where ``hypothesis`` cannot be installed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encode as enc
from repro.core import fz, metrics, quant, shuffle

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # hermetic box: the seeded fallback tier below still runs
    HAVE_HYPOTHESIS = False

SET = dict(max_examples=25, deadline=None)
KINDS = ("normal", "smooth", "constant", "zeros")
EBS = (1e-2, 1e-3, 1e-4, 1e-5)


def make_array(seed: int, kind: str, dims) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "normal":
        x = rng.standard_normal(dims)
    elif kind == "smooth":
        x = rng.standard_normal(dims)
        for ax in range(len(dims)):
            x = np.cumsum(x, axis=ax) * 0.1
    elif kind == "constant":
        x = np.full(dims, rng.uniform(-100, 100))
    else:
        x = np.zeros(dims)
    return x.astype(np.float32)


# ---------------------------------------------------------------------------
# Checkers (shared by both tiers)
# ---------------------------------------------------------------------------

def check_error_bound_invariant(x: np.ndarray, eb: float) -> None:
    """|x - D(C(x))|_inf <= eb_abs with exact outliers ON (strict mode).

    The bound is exact in real arithmetic; the float32 reconstruction
    ``code * 2eb`` adds up to ~|x|_inf * 2^-22 of rounding noise on top
    (visible at tight bounds on O(1) data, e.g. eb=1e-5 on |x| ~ 4 — found
    by the property search once it actually ran), so the tolerance carries
    an explicit f32-rounding allowance rather than a magic slack factor.
    """
    cfg = fz.FZConfig(eb=eb, eb_mode="rel", exact_outliers=True, outlier_frac=1.0)
    rec, c = fz.roundtrip(jnp.asarray(x), cfg)
    eb_abs = float(c.eb_abs)
    f32_round = float(np.max(np.abs(x), initial=0.0)) * 2.0 ** -22
    assert float(metrics.max_abs_err(jnp.asarray(x), rec)) \
        <= eb_abs * 1.001 + f32_round + 1e-30


def check_compression_ratio_accounting(x: np.ndarray, eb: float) -> None:
    """used_bytes is positive and nnz never exceeds the block count."""
    cfg = fz.FZConfig(eb=eb)
    c = fz.compress(jnp.asarray(x), cfg)
    used = int(c.used_bytes())
    assert used > 0
    assert int(c.nnz_blocks) <= fz.FZConfig.n_blocks(x.size)


def check_bitshuffle_involution(seed: int, n_tiles: int) -> None:
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, 1 << 16, size=n_tiles * shuffle.TILE,
                                     dtype=np.uint16))
    assert jnp.array_equal(shuffle.bitunshuffle(shuffle.bitshuffle(codes)), codes)


def check_transpose16_involution(seed: int) -> None:
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 1 << 16, size=(32, 16), dtype=np.uint16))
    assert jnp.array_equal(shuffle.transpose16(shuffle.transpose16(x)), x)


def check_encoder_roundtrip_exact(seed: int, density: float) -> None:
    """encode/decode is lossless when capacity >= nnz (any sparsity)."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 1 << 16, size=4096, dtype=np.uint16)
    mask = rng.random(4096 // 8) < density
    words = words.reshape(-1, 8) * mask[:, None]
    words = jnp.asarray(words.reshape(-1).astype(np.uint16))
    n_blocks = words.size // enc.BLOCK_WORDS
    bitflags, payload, nnz = enc.encode(words, capacity=n_blocks)
    dec = enc.decode(bitflags, payload, n_blocks=n_blocks)
    assert jnp.array_equal(dec, words)
    assert int(nnz) == int(jnp.sum(jnp.any(words.reshape(-1, 8) != 0, axis=1)))


def check_lorenzo_inverse_exact(seed: int) -> None:
    rng = np.random.default_rng(seed)
    for shape in [(100,), (17, 23), (5, 7, 11)]:
        q = jnp.asarray(rng.integers(-1000, 1000, size=shape, dtype=np.int32))
        assert jnp.array_equal(quant.lorenzo_inverse(quant.lorenzo_delta(q)), q)


def check_code_roundtrip(seed: int, mode: str) -> None:
    rng = np.random.default_rng(seed)
    d = jnp.asarray(rng.integers(-32767, 32768, size=1000, dtype=np.int32))
    codes, over, resid = quant.to_codes(d, code_mode=mode)
    assert not bool(jnp.any(over))
    assert bool(jnp.all(resid == 0))
    assert jnp.array_equal(quant.from_codes(codes, code_mode=mode), d)


def check_monotone_ratio_in_eb(seed: int) -> None:
    """Looser error bounds never compress worse (same data)."""
    rng = np.random.default_rng(seed)
    x = np.cumsum(rng.standard_normal((64, 64)).astype(np.float32), axis=0)
    crs = []
    for eb in (1e-4, 1e-3, 1e-2):
        c = fz.compress(jnp.asarray(x), fz.FZConfig(eb=eb))
        crs.append(float(c.compression_ratio()))
    assert crs[0] <= crs[1] * 1.01 and crs[1] <= crs[2] * 1.01, crs


def _three_way_cfgs(code_mode: str, eb: float, eb_mode: str = "rel"):
    base = dict(eb=eb, eb_mode=eb_mode, code_mode=code_mode,
                exact_outliers=False)
    return {"reference": fz.FZConfig(**base),
            "staged": fz.FZConfig(**base, use_kernels=True,
                                  kernel_mode="staged"),
            "fused": fz.FZConfig(**base, use_kernels=True,
                                 kernel_mode="fused")}


def check_three_way_bit_identity(x: np.ndarray, eb: float,
                                 code_mode: str = "sign_mag") -> None:
    """fused == staged == reference: bitflags, payload, nnz AND roundtrip are
    bit-identical across the three execution paths on the same data."""
    data = jnp.asarray(x)
    outs = {name: fz.roundtrip(data, cfg)
            for name, cfg in _three_way_cfgs(code_mode, eb).items()}
    rec0, c0 = outs["reference"]
    for name in ("staged", "fused"):
        rec, c = outs[name]
        assert jnp.array_equal(c0.bitflags, c.bitflags), name
        assert jnp.array_equal(c0.payload, c.payload), name
        assert int(c0.nnz_blocks) == int(c.nnz_blocks), name
        assert jnp.array_equal(rec0, rec), name


def check_three_way_shared_eb_vmap(seed: int, page_shape, eb_abs: float,
                                   code_mode: str = "sign_mag") -> None:
    """compress_with_eb pages under vmap (the kvpool batched dispatch): all
    three paths produce bit-identical stacked containers, and each path's
    vmapped dispatch is bit-identical to its own single-page calls."""
    rng = np.random.default_rng(seed)
    pages = jnp.asarray(np.cumsum(
        rng.standard_normal((3, *page_shape)), axis=-1).astype(np.float32))
    eb = jnp.float32(eb_abs)
    stacked = {}
    for name, cfg in _three_way_cfgs(code_mode, 1.0, eb_mode="abs").items():
        batched = jax.vmap(lambda d: fz.compress_with_eb(d, eb, cfg))(pages)
        singles = [fz.compress_with_eb(pages[i], eb, cfg) for i in range(3)]
        for i, s in enumerate(singles):
            assert jnp.array_equal(batched.bitflags[i], s.bitflags), name
            assert jnp.array_equal(batched.payload[i], s.payload), name
        recs = jax.vmap(lambda c: fz.decompress(c, cfg))(batched)
        for i, s in enumerate(singles):
            assert jnp.array_equal(recs[i], fz.decompress(s, cfg)), name
        stacked[name] = (batched, recs)
    b0, r0 = stacked["reference"]
    for name in ("staged", "fused"):
        b, r = stacked[name]
        assert jnp.array_equal(b0.bitflags, b.bitflags), name
        assert jnp.array_equal(b0.payload, b.payload), name
        assert jnp.array_equal(r0, r), name


# ---------------------------------------------------------------------------
# Tier 1: hypothesis-driven search (skipped wholesale when unavailable)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    def arrays(draw, max_elems=20_000):
        ndim = draw(st.integers(1, 3))
        dims = draw(st.lists(st.integers(1, 40), min_size=ndim, max_size=ndim))
        n = int(np.prod(dims))
        if n > max_elems:
            dims = [min(d, 16) for d in dims]
        seed = draw(st.integers(0, 2**31 - 1))
        kind = draw(st.sampled_from(list(KINDS)))
        return make_array(seed, kind, dims)

    @st.composite
    def field_and_eb(draw):
        return arrays(draw), draw(st.sampled_from(list(EBS)))

    @given(field_and_eb())
    @settings(**SET)
    def test_error_bound_invariant(case):
        check_error_bound_invariant(*case)

    @given(field_and_eb())
    @settings(**SET)
    def test_compression_ratio_accounting(case):
        check_compression_ratio_accounting(*case)

    @given(st.integers(0, 2**31 - 1), st.integers(1, 6))
    @settings(**SET)
    def test_bitshuffle_involution(seed, n_tiles):
        check_bitshuffle_involution(seed, n_tiles)

    @given(st.integers(0, 2**31 - 1))
    @settings(**SET)
    def test_transpose16_is_involution(seed):
        check_transpose16_involution(seed)

    @given(st.integers(0, 2**31 - 1), st.floats(0.0, 0.9))
    @settings(**SET)
    def test_encoder_roundtrip_exact(seed, density):
        check_encoder_roundtrip_exact(seed, density)

    @given(st.integers(0, 2**31 - 1))
    @settings(**SET)
    def test_lorenzo_inverse_exact(seed):
        check_lorenzo_inverse_exact(seed)

    @given(st.integers(0, 2**31 - 1), st.sampled_from(["sign_mag", "zigzag"]))
    @settings(**SET)
    def test_code_roundtrip(seed, mode):
        check_code_roundtrip(seed, mode)

    @given(st.integers(0, 2**31 - 1))
    @settings(**SET)
    def test_monotone_ratio_in_eb(seed):
        check_monotone_ratio_in_eb(seed)

    @st.composite
    def field_eb_mode(draw):
        # three Pallas compiles per example: fewer, fatter cases
        return (arrays(draw, max_elems=12_000),
                draw(st.sampled_from([1e-2, 1e-3, 1e-4])),
                draw(st.sampled_from(["sign_mag", "zigzag"])))

    @given(field_eb_mode())
    @settings(max_examples=10, deadline=None)
    def test_three_way_bit_identity(case):
        check_three_way_bit_identity(*case)


def test_importorskip_guard():
    """Document the dependency: everything above this line must not require
    hypothesis at collection time; this canary is the only test that does."""
    pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# Tier 2: seeded np.random fallback (always runs; fixed case matrix)
# ---------------------------------------------------------------------------

_FALLBACK_CASES = [
    (seed, kind, dims, eb)
    for seed, (kind, dims, eb) in enumerate([
        ("normal", (40,), 1e-3), ("normal", (17, 23), 1e-4),
        ("smooth", (20_000,), 1e-4), ("smooth", (64, 64), 1e-5),
        ("smooth", (16, 16, 16), 1e-3), ("constant", (7, 11), 1e-2),
        ("zeros", (33,), 1e-3), ("normal", (5, 7, 11), 1e-2),
    ])
]


@pytest.mark.parametrize("seed,kind,dims,eb", _FALLBACK_CASES)
def test_error_bound_invariant_seeded(seed, kind, dims, eb):
    check_error_bound_invariant(make_array(seed, kind, list(dims)), eb)


@pytest.mark.parametrize("seed,kind,dims,eb", _FALLBACK_CASES)
def test_compression_ratio_accounting_seeded(seed, kind, dims, eb):
    check_compression_ratio_accounting(make_array(seed, kind, list(dims)), eb)


@pytest.mark.parametrize("seed,n_tiles", [(0, 1), (1, 3), (2, 6)])
def test_bitshuffle_involution_seeded(seed, n_tiles):
    check_bitshuffle_involution(seed, n_tiles)


@pytest.mark.parametrize("seed", range(3))
def test_transpose16_is_involution_seeded(seed):
    check_transpose16_involution(seed)


@pytest.mark.parametrize("seed,density", [(0, 0.0), (1, 0.3), (2, 0.9)])
def test_encoder_roundtrip_exact_seeded(seed, density):
    check_encoder_roundtrip_exact(seed, density)


@pytest.mark.parametrize("use_kernels", [False, True], ids=["ref", "kernels"])
@pytest.mark.parametrize("kind,dims,eb", [("smooth", (40, 50, 60), 1e-3),
                                          ("turbulent", (64, 64, 16), 1e-4),
                                          ("smooth", (300, 700), 1e-3),
                                          ("particle", (50_000,), 1e-4)])
def test_strict_bound_holds_without_rounding_allowance(kind, dims, eb,
                                                       use_kernels):
    """With the snapped bound and |q| < 2**16 the strict mode holds
    max_abs_err <= eb_abs exactly, on the reference and the kernel path."""
    from repro.data import make_field
    x = jnp.asarray(make_field(kind, dims, seed=0))
    cfg = fz.FZConfig(eb=eb, eb_mode="rel", exact_outliers=True,
                      use_kernels=use_kernels, kernel_mode="staged")
    rec, c = fz.roundtrip(x, cfg)
    assert float(metrics.max_abs_err(x, rec)) <= float(c.eb_abs)


def test_inv_two_eb_is_the_correctly_rounded_reciprocal():
    """The integer long division equals IEEE float32 1/(2eb) bit for bit,
    over log-uniform bounds and every power of two in range."""
    rng = np.random.default_rng(0)
    eb = np.concatenate([10.0 ** rng.uniform(-30, 30, 20000),
                         2.0 ** np.arange(-99, 100)]).astype(np.float32)
    got = np.asarray(jax.jit(quant.inv_two_eb)(jnp.asarray(eb)))
    want = np.float32(1) / (np.float32(2) * eb)
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("seed", range(3))
def test_lorenzo_inverse_exact_seeded(seed):
    check_lorenzo_inverse_exact(seed)


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("mode", ["sign_mag", "zigzag"])
def test_code_roundtrip_seeded(seed, mode):
    check_code_roundtrip(seed, mode)


@pytest.mark.parametrize("seed", range(2))
def test_monotone_ratio_in_eb_seeded(seed):
    check_monotone_ratio_in_eb(seed)


# three-way fused == staged == reference: 1/2/3D, non-tile-multiple sizes,
# both code modes (the full kernel_mode matrix of core/fz.py)
_THREE_WAY_CASES = [
    ("normal", (40,), 1e-3, "sign_mag"),          # sub-tile 1D
    ("smooth", (10_001,), 1e-4, "sign_mag"),      # non-tile-multiple 1D
    ("smooth", (10_001,), 1e-4, "zigzag"),
    ("smooth", (17, 23), 1e-3, "sign_mag"),       # tiny odd 2D
    ("smooth", (33, 1000), 1e-4, "zigzag"),       # tile-straddling rows
    ("normal", (64, 64), 1e-2, "sign_mag"),       # exactly one tile
    ("smooth", (16, 16, 16), 1e-3, "sign_mag"),   # 3D
    ("normal", (5, 7, 11), 1e-2, "zigzag"),       # tiny odd 3D
    ("zeros", (4096,), 1e-3, "sign_mag"),         # all-zero stream
    ("constant", (7, 11), 1e-2, "sign_mag"),
]


@pytest.mark.parametrize("kind,dims,eb,code_mode", _THREE_WAY_CASES)
def test_three_way_bit_identity_seeded(kind, dims, eb, code_mode):
    check_three_way_bit_identity(make_array(0, kind, list(dims)), eb,
                                 code_mode)


@pytest.mark.parametrize("page_shape,code_mode",
                         [((8192,), "sign_mag"), ((4, 2048), "zigzag")])
def test_three_way_shared_eb_vmap_seeded(page_shape, code_mode):
    check_three_way_shared_eb_vmap(11, page_shape, 0.01, code_mode)


def test_paper_mode_matches_strict_when_no_outliers():
    rng = np.random.default_rng(0)
    x = jnp.asarray((np.cumsum(rng.standard_normal(30_000)) * 0.01).astype(np.float32))
    strict = fz.FZConfig(eb=1e-3, exact_outliers=True)
    paper = fz.FZConfig(eb=1e-3, exact_outliers=False)
    rs, cs = fz.roundtrip(x, strict)
    rp, cp = fz.roundtrip(x, paper)
    assert int(cs.n_outliers) == 0
    assert jnp.array_equal(rs, rp)
