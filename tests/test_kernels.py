"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encode as enc
from repro.core import fz, metrics, quant, shuffle
from repro.kernels import bitshuffle_flag as bsf
from repro.kernels import fused_compress as fc
from repro.kernels import fused_decode as fd
from repro.kernels import lorenzo_quant as lq
from repro.kernels import ops, ref
from repro.launch import hlo_cost

RNG = np.random.default_rng(42)


def _eb(v):
    """A bound as the compressor uses it: snapped (``quant.snap_eb``)."""
    return quant.snap_eb(jnp.float32(v))


@pytest.mark.parametrize("n_tiles", [1, 2, 8, 9, 17])
def test_bitshuffle_flag_matches_oracle(n_tiles):
    codes = jnp.asarray(RNG.integers(0, 1 << 16, size=(n_tiles, ref.TILE), dtype=np.uint16))
    sh_k, fl_k = bsf.bitshuffle_flag(codes, interpret=True)
    sh_r, fl_r = ref.bitshuffle_flag_ref(codes)
    np.testing.assert_array_equal(np.asarray(sh_k), np.asarray(sh_r))
    np.testing.assert_array_equal(np.asarray(fl_k), np.asarray(fl_r))


@pytest.mark.parametrize("n_tiles", [1, 3, 8])
def test_unshuffle_kernel_roundtrip(n_tiles):
    codes = jnp.asarray(RNG.integers(0, 1 << 16, size=(n_tiles, ref.TILE), dtype=np.uint16))
    sh, _ = bsf.bitshuffle_flag(codes, interpret=True)
    back = bsf.bitunshuffle_tiles(sh, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))


def test_unshuffle_matches_reference_oracle():
    codes = jnp.asarray(RNG.integers(0, 1 << 16, size=(4, ref.TILE), dtype=np.uint16))
    sh_r, _ = ref.bitshuffle_flag_ref(codes)
    back = bsf.bitunshuffle_tiles(sh_r, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(ref.bitunshuffle_ref(sh_r)))


@pytest.mark.parametrize("shape", [(7,), (4096,), (10_001,), (64, 64), (33, 1000),
                                   (16, 32, 48), (65, 7, 129), (1, 1, 1)])
@pytest.mark.parametrize("code_mode", ["sign_mag", "zigzag"])
def test_lorenzo_quant_matches_oracle(shape, code_mode):
    x = jnp.asarray(RNG.standard_normal(shape).astype(np.float32))
    k = lq.lorenzo_quant(x, _eb(1e-3), code_mode=code_mode, interpret=True)
    r = ref.lorenzo_quant_ref(x, _eb(1e-3), code_mode=code_mode)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


@pytest.mark.parametrize("eb", [1e-2, 1e-4, 3.7e-3])
def test_lorenzo_quant_eb_sweep(eb):
    x = jnp.asarray(np.cumsum(RNG.standard_normal((50, 70)), axis=0).astype(np.float32))
    k = lq.lorenzo_quant(x, _eb(eb), interpret=True)
    r = ref.lorenzo_quant_ref(x, _eb(eb))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


def test_saturation_on_rough_data():
    """Kernel saturates exactly like the reference on outlier-heavy data."""
    x = jnp.asarray(RNG.standard_normal((100, 100)).astype(np.float32) * 1e4)
    k = lq.lorenzo_quant(x, _eb(1e-4), interpret=True)
    r = ref.lorenzo_quant_ref(x, _eb(1e-4))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


@pytest.mark.parametrize("code_mode", ["sign_mag", "zigzag"])
@pytest.mark.parametrize("shape", [(5000,), (40, 300), (9, 20, 130)],
                         ids=["1d", "2d", "3d"])
def test_residual_output_matches_reference_outliers(shape, code_mode):
    """The kernel's strict-mode residuals, compacted, are the reference's
    exact-outlier channel, on data rough enough to saturate often."""
    x = jnp.asarray(RNG.standard_normal(shape).astype(np.float32) * 1e4)
    eb = _eb(1e-2)
    codes, resid = lq.lorenzo_quant(x, eb, code_mode=code_mode,
                                    with_residual=True, interpret=True)
    K = x.size
    want = quant.dual_quantize(x, eb, code_mode=code_mode, outlier_capacity=K)
    got = (codes, *quant.collect_outliers(resid, K))
    assert int(want[3]) > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fz_kernel_path_bit_identical_to_reference():
    x = jnp.asarray(np.cumsum(RNG.standard_normal((128, 128)), axis=1).astype(np.float32))
    cfg_k = fz.FZConfig(eb=1e-3, use_kernels=True, exact_outliers=False)
    cfg_r = fz.FZConfig(eb=1e-3, use_kernels=False, exact_outliers=False)
    rk, ck = fz.roundtrip(x, cfg_k)
    rr, cr = fz.roundtrip(x, cfg_r)
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(ck.bitflags), np.asarray(cr.bitflags))
    np.testing.assert_array_equal(np.asarray(ck.payload), np.asarray(cr.payload))
    assert int(ck.nnz_blocks) == int(cr.nnz_blocks)


@pytest.mark.parametrize("kernel_mode", ["staged", "fused"])
def test_fz_kernel_hybrid_strict_mode(kernel_mode):
    """use_kernels + exact_outliers: the quantization kernel also writes
    the residuals (ops.lorenzo_quantize / fused_compress_stages), every
    stage stays a kernel, and the strict bound holds."""
    x = jnp.asarray(RNG.standard_normal((64, 200)).astype(np.float32) * 50)
    cfg = fz.FZConfig(eb=1e-4, use_kernels=True, kernel_mode=kernel_mode,
                      exact_outliers=True, outlier_frac=1.0)
    rec, c = fz.roundtrip(x, cfg)
    assert float(metrics.max_abs_err(x, rec)) <= float(c.eb_abs) * (1 + 1e-5)


@pytest.mark.parametrize("kernel_mode", ["staged", "fused"])
def test_fz_kernel_strict_mode_with_real_saturation(kernel_mode):
    """Spiky field whose deltas overflow u16: the outlier side channel must
    actually fire (n_outliers > 0) and still restore the strict bound on the
    kernel paths — pins the explicit raise-or-route contract of the fused
    entry (exact outliers can never silently degrade to saturation)."""
    base = RNG.standard_normal(30_000).astype(np.float32) * 0.01
    spikes = (RNG.random(30_000) < 0.01) * \
        RNG.standard_normal(30_000).astype(np.float32) * 100.0
    x = jnp.asarray(base + spikes)
    cfg = fz.FZConfig(eb=1e-5, eb_mode="abs", use_kernels=True,
                      kernel_mode=kernel_mode, exact_outliers=True,
                      outlier_frac=1.0)
    rec, c = fz.roundtrip(x, cfg)
    assert int(c.n_outliers) > 0
    f32_round = float(jnp.max(jnp.abs(x))) * 2.0 ** -22
    assert float(metrics.max_abs_err(x, rec)) \
        <= float(c.eb_abs) * 1.001 + f32_round
    # and the reconstruction is bit-identical to the reference path
    rec_r, _ = fz.roundtrip(x, fz.FZConfig(
        eb=1e-5, eb_mode="abs", exact_outliers=True, outlier_frac=1.0))
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(rec_r))


# ---------------------------------------------------------------------------
# flash-decode kernel vs the dist/flash_decode jnp partials (the oracle)
# ---------------------------------------------------------------------------

def _decode_case(seed, B=4, S=96, H=8, KVH=4, D=16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, KVH, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, KVH, D)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("kv_tile", [16, 32, 64, 128])  # 64: pads 96 -> 128;
def test_flash_decode_partials_match_jnp_oracle(kv_tile):  # 128 clamps to S=96
    from repro.dist import flash_decode as fdr
    from repro.kernels import flash_decode as fdk
    q, k, v = _decode_case(0)
    length = jnp.asarray([0, 1, 96, 37], jnp.int32)  # empty / one / full / ragged
    m_k, num_k, den_k = fdk.decode_partials(q, k, v, length, kv_tile=kv_tile,
                                            interpret=True)
    m_r, num_r, den_r = fdr.decode_partials(q, k, v, length, shard_offset=0)
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))  # max is exact
    np.testing.assert_allclose(np.asarray(num_k), np.asarray(num_r), atol=2e-4)
    np.testing.assert_allclose(np.asarray(den_k), np.asarray(den_r), atol=2e-4)


@pytest.mark.parametrize("offset", [0, 32, 80])      # 80: slice past every length
def test_flash_decode_shard_offset_matches_oracle(offset):
    """Offset slices (the shard_map per-shard view) mask identically."""
    from repro.dist import flash_decode as fdr
    from repro.kernels import flash_decode as fdk
    q, k, v = _decode_case(1)
    length = jnp.asarray([5, 40, 64, 96], jnp.int32)
    ksl, vsl = k[:, offset:], v[:, offset:]
    got = fdk.decode_partials(q, ksl, vsl, length, shard_offset=offset,
                              kv_tile=16, interpret=True)
    want = fdr.decode_partials(q, ksl, vsl, length, shard_offset=offset)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4)


def test_flash_decode_padded_tile_with_overlong_length():
    """Regression: tile padding must stay masked even when the global length
    extends past this slice (a shard whose sequence continues in later
    shards). With S=96, kv_tile=64 the slice pads to 128; an unclamped
    ``pos < length`` mask would let the 32 zero-K pad rows into the softmax
    (each adds exp(-m) to den), skewing den by O(pad)."""
    from repro.dist import flash_decode as fdr
    from repro.kernels import flash_decode as fdk
    q, k, v = _decode_case(7)
    length = jnp.asarray([200, 96, 97, 5], jnp.int32)   # all >= or > slice end
    got = fdk.decode_partials(q, k, v, length, shard_offset=0, kv_tile=64,
                              interpret=True)
    want = fdr.decode_partials(q, k, v, length, shard_offset=0)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=2e-4)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]), atol=2e-4)


def test_flash_decode_combined_matches_decode_attention():
    from repro.kernels import flash_decode as fdk
    from repro.models.attention import decode_attention
    q, k, v = _decode_case(2)
    length = jnp.asarray([1, 17, 96, 50], jnp.int32)
    out = fdk.flash_decode(q, k, v, length, kv_tile=32, interpret=True)
    ref = decode_attention(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_flash_decode_paged_layout_matches_contiguous():
    """Page-native entry == contiguous entry == oracle (same data, two tilings)."""
    from repro.kernels import flash_decode as fdk
    from repro.models.attention import decode_attention
    q, k, v = _decode_case(3)
    length = jnp.asarray([0, 16, 96, 49], jnp.int32)  # page-aligned + straddling
    B, S, KVH, D = k.shape
    ps = 16
    kp = k.reshape(B, S // ps, ps, KVH, D)
    vp = v.reshape(B, S // ps, ps, KVH, D)
    m, num, den = fdk.decode_partials_pages(q, kp, vp, length, interpret=True)
    out = fdk.combine_partials(m, num, den, dtype=q.dtype)
    ref = decode_attention(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(ref[1:]), atol=2e-4)
    # length-0 lane: kernel returns exactly 0 (num == den == 0) — the
    # contiguous oracle's unmasked softmax degenerates to a mean there
    assert np.all(np.asarray(out[0]) == 0.0)


def test_flash_decode_all_lanes_empty_is_zero():
    """All slices empty: the renorm weight is exp(0) == 1, yet the output is
    exactly 0 because num and den are both 0 — the contract the combine
    comments document (dist/flash_decode.py, kvpool/attention.py)."""
    from repro.dist import flash_decode as fdr
    from repro.kernels import flash_decode as fdk
    q, k, v = _decode_case(4, B=2, S=32)
    length = jnp.zeros((2,), jnp.int32)
    out = fdk.flash_decode(q, k, v, length, kv_tile=16, interpret=True)
    assert np.all(np.asarray(out) == 0.0)
    m, num, den = fdk.decode_partials(q, k, v, length, kv_tile=16, interpret=True)
    assert np.all(np.asarray(m) == fdk.NEG_INF)
    assert np.all(np.asarray(num) == 0.0) and np.all(np.asarray(den) == 0.0)
    # and the jnp reference partials agree exactly on the empty contract
    m_r, num_r, den_r = fdr.decode_partials(q, k, v, length, shard_offset=0)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(num), np.asarray(num_r))


def test_ops_shuffle_encode_equals_core_encode():
    from repro.core import encode as enc, shuffle as shf
    codes = jnp.asarray(RNG.integers(0, 1 << 16, size=3 * ref.TILE, dtype=np.uint16))
    cap = codes.size // enc.BLOCK_WORDS
    bf_k, pl_k, nnz_k = ops.bitshuffle_flag_encode(codes, capacity=cap)
    bf_r, pl_r, nnz_r = enc.encode(shf.bitshuffle(codes), capacity=cap)
    np.testing.assert_array_equal(np.asarray(bf_k), np.asarray(bf_r))
    np.testing.assert_array_equal(np.asarray(pl_k), np.asarray(pl_r))
    assert int(nnz_k) == int(nnz_r)


# ---------------------------------------------------------------------------
# fused megakernels vs the composed reference stages (the heavy shape x mode
# coverage lives in the three-way property suite; these pin the kernel-level
# contracts directly)
# ---------------------------------------------------------------------------

def _ref_compress(x, eb, code_mode, capacity):
    codes, _, _, _ = quant.dual_quantize(x, eb, code_mode=code_mode,
                                         outlier_capacity=0)
    flat = shuffle.pad_to_tiles(codes.reshape(-1))
    return enc.encode(shuffle.bitshuffle(flat), capacity=capacity)


@pytest.mark.parametrize("shape", [(10_001,), (33, 1000), (16, 16, 16)])
@pytest.mark.parametrize("code_mode", ["sign_mag", "zigzag"])
def test_fused_compress_matches_composed_reference(shape, code_mode):
    x = jnp.asarray(np.cumsum(RNG.standard_normal(shape), axis=0)
                    .astype(np.float32) * 0.3)
    eb = _eb(1e-3)
    cap = fc.plan_stream(shape).padded_n // enc.BLOCK_WORDS
    bf_r, pl_r, nnz_r = _ref_compress(x, eb, code_mode, cap)
    bf_k, pl_k, nnz_k = fc.fused_compress(x, eb, capacity=cap,
                                          code_mode=code_mode, interpret=True)
    np.testing.assert_array_equal(np.asarray(bf_k), np.asarray(bf_r))
    np.testing.assert_array_equal(np.asarray(pl_k), np.asarray(pl_r))
    assert int(nnz_k) == int(nnz_r)


def test_fused_compress_bounded_capacity_drops_like_reference():
    x = jnp.asarray(np.cumsum(RNG.standard_normal(20_000))
                    .astype(np.float32) * 0.3)
    eb = _eb(1e-4)
    bf_r, pl_r, nnz_r = _ref_compress(x, eb, "sign_mag", 100)
    bf_k, pl_k, nnz_k = fc.fused_compress(x, eb, capacity=100, interpret=True)
    np.testing.assert_array_equal(np.asarray(bf_k), np.asarray(bf_r))
    np.testing.assert_array_equal(np.asarray(pl_k), np.asarray(pl_r))
    assert int(nnz_k) == int(nnz_r) and int(nnz_k) > 100


def test_fused_shuffle_encode_matches_core_encode():
    codes = jnp.asarray(RNG.integers(0, 1 << 16, size=9 * ref.TILE, dtype=np.uint16))
    codes = jnp.where(jnp.asarray(RNG.random(codes.size) < 0.7), 0,
                      codes).astype(jnp.uint16)
    cap = codes.size // enc.BLOCK_WORDS
    bf_r, pl_r, nnz_r = enc.encode(shuffle.bitshuffle(codes), capacity=cap)
    bf_k, pl_k, nnz_k = fc.fused_shuffle_encode(codes, capacity=cap,
                                                interpret=True)
    np.testing.assert_array_equal(np.asarray(bf_k), np.asarray(bf_r))
    np.testing.assert_array_equal(np.asarray(pl_k), np.asarray(pl_r))
    assert int(nnz_k) == int(nnz_r)


@pytest.mark.parametrize("shape", [(20_000,), (65, 7, 129)])
def test_fused_decompress_matches_composed_reference(shape):
    x = jnp.asarray(np.cumsum(RNG.standard_normal(shape), axis=0)
                    .astype(np.float32) * 0.3)
    eb = _eb(1e-3)
    cap = fc.plan_stream(shape).padded_n // enc.BLOCK_WORDS
    bf, pld, _ = fc.fused_compress(x, eb, capacity=cap, interpret=True)
    words = enc.decode(bf, pld, n_blocks=fz.FZConfig.n_blocks(x.size))
    codes = shuffle.bitunshuffle(words)[: x.size]
    want = quant.dual_dequantize(codes, eb, tuple(shape))
    got = fd.fused_decompress(bf, pld, eb, shape=tuple(shape), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_decompress_applies_outlier_residuals_in_kernel():
    base = RNG.standard_normal((120, 170)).astype(np.float32) * 0.01
    spikes = (RNG.random((120, 170)) < 0.01) * \
        RNG.standard_normal((120, 170)).astype(np.float32) * 100.0
    x = jnp.asarray(base + spikes)
    eb = _eb(1e-5)
    K = x.size // 8
    codes, oidx, oval, n_over = quant.dual_quantize(x, eb, outlier_capacity=K)
    assert int(n_over) > 0
    cap = fc.plan_stream(x.shape).padded_n // enc.BLOCK_WORDS
    flat = shuffle.pad_to_tiles(codes.reshape(-1))
    bf, pld, _ = fc.fused_shuffle_encode(flat, capacity=cap, interpret=True)
    dec_codes = shuffle.bitunshuffle(
        enc.decode(bf, pld, n_blocks=fz.FZConfig.n_blocks(x.size)))[: x.size]
    want = quant.dual_dequantize(dec_codes, eb, x.shape,
                                 outlier_idx=oidx, outlier_val=oval)
    got = fd.fused_decompress(bf, pld, eb, shape=x.shape,
                              outlier_idx=oidx, outlier_val=oval,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the data-movement claim, pinned mechanically (issue acceptance criterion)
# ---------------------------------------------------------------------------

_TRAFFIC_SHAPE = (256, 1024)     # 1 MiB f32, already a TILE multiple


def _traffic_cfg(kernel_mode, capacity_frac=1.0):
    return fz.FZConfig(eb=1e-3, use_kernels=True, kernel_mode=kernel_mode,
                       exact_outliers=False, capacity_frac=capacity_frac)


def test_fused_compress_materializes_no_code_stream_buffer():
    """§3.5 fusion claim, compress side: the staged path materializes the u16
    code stream AND the shuffled-word stream in HBM (XLA buffers of >= one
    full stream length); the fused megakernel's optimized HLO contains NO
    u16 buffer that large — the streams live in VMEM scratch. capacity_frac
    keeps the (legitimate, output) payload below the stream size so the scan
    is a pure intermediate-stream detector."""
    x = jnp.zeros(_TRAFFIC_SHAPE, jnp.float32)
    stream_elems = fz.FZConfig.padded_n(x.size)
    shapes = {}
    for mode in ("staged", "fused"):
        cfg = _traffic_cfg(mode, capacity_frac=0.5)
        txt = jax.jit(lambda d, cfg=cfg: fz.compress(d, cfg)) \
            .lower(x).compile().as_text()
        shapes[mode] = hlo_cost.materialized_shapes(
            txt, dtype="u16", min_elems=stream_elems)
    assert len(shapes["staged"]) >= 2, \
        f"staged path should round-trip code + word streams: {shapes['staged']}"
    assert not shapes["fused"], \
        f"fused compress materialized stream-sized buffers: {shapes['fused']}"


def test_fused_decompress_hbm_traffic_is_io_bound():
    """§3.5 fusion claim, decode side (the kvpool transient-read hot path):
    buffer-assignment traffic of the fused megakernel stays within ~1.3x of
    the unavoidable argument+output bytes, while the staged path (word and
    code streams through HBM) costs >= ~2.4x."""
    x = jnp.asarray(np.cumsum(RNG.standard_normal(_TRAFFIC_SHAPE), axis=1)
                    .astype(np.float32))
    ratios = {}
    for mode in ("staged", "fused"):
        cfg = _traffic_cfg(mode)
        c = fz.compress(x, cfg)
        compiled = jax.jit(lambda cc, cfg=cfg: fz.decompress(cc, cfg)) \
            .lower(c).compile()
        ratios[mode] = hlo_cost.compiled_memory_traffic(compiled)["traffic_ratio"]
    assert ratios["fused"] <= 1.3, ratios
    assert ratios["staged"] >= 2.4, ratios
    # decompressions agree bit-exactly while moving ~2x fewer bytes
    assert ratios["staged"] / ratios["fused"] >= 1.8, ratios
