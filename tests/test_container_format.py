"""Container-format tests: serialization roundtrips, versioning, fixtures.

The frozen fixtures under tests/data/ are *checked-in bytes* written by the
format version current at their generation time (see tests/data/
make_fixtures.py). They must keep decompressing bit-exactly forever: a
failure here means the format changed without a version bump — fix the
reader, never the fixture. Byte layout: docs/CONTAINER_FORMAT.md.
"""
import pathlib
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fz
from repro.data import make_field
from repro.serve.kvpool import PagePool, PoolConfig

DATA = pathlib.Path(__file__).parent / "data"


def _field(shape=(20, 20, 10), kind="smooth", seed=9):
    return jnp.asarray(make_field(kind, shape, seed=seed))


@pytest.mark.parametrize("code_mode", ["sign_mag", "zigzag"])
@pytest.mark.parametrize("exact_outliers", [True, False])
@pytest.mark.parametrize("entropy", [False, True, "auto"])
def test_roundtrip_matrix(code_mode, exact_outliers, entropy):
    f = _field()
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel", code_mode=code_mode,
                      exact_outliers=exact_outliers)
    comp = fz.compress(f, cfg)
    raw = fz.to_bytes(comp, cfg, entropy=entropy)
    back, back_cfg = fz.from_bytes(raw)
    assert back_cfg.code_mode == code_mode
    assert back_cfg.exact_outliers == exact_outliers
    assert back.shape == comp.shape and back.dtype_name == comp.dtype_name
    assert jnp.array_equal(fz.decompress_bytes(raw), fz.decompress(comp, cfg))


def test_deserialized_container_is_leaf_identical():
    """from_bytes at the original capacities reproduces the compressed pytree
    leaf-for-leaf — the property that lets blob-backed pages vmap-stack next
    to never-serialized ones in the kvpool."""
    f = _field()
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel")
    comp = fz.compress(f, cfg)
    raw = fz.to_bytes(comp, cfg, entropy=True)
    back, _ = fz.from_bytes(raw, capacity=int(comp.payload.shape[1]),
                            outlier_capacity=int(comp.outlier_idx.shape[0]))
    for a, b in zip(jax.tree.leaves(comp), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_entropy_flag_recorded_and_routed():
    f = _field()
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel")
    comp = fz.compress(f, cfg)
    for entropy, expect in ((False, False), (True, True)):
        raw = fz.to_bytes(comp, cfg, entropy=entropy)
        flags = struct.unpack_from("<H", raw, 6)[0]
        assert bool(flags & fz.FLAG_ENTROPY) is expect


def test_auto_probe_skips_incompressible():
    """Near-uniform payload bytes: the exact-size probe must refuse the
    entropy stage and keep the raw payload."""
    rng = np.random.default_rng(0)
    noise = jnp.asarray(rng.standard_normal(8192), jnp.float32)
    # white noise at a moderate bound: the compacted payload's byte histogram
    # is flat enough that the exact-size probe predicts < ENTROPY_MIN_GAIN
    cfg = fz.FZConfig(eb=1e-4, eb_mode="rel")
    comp = fz.compress(noise, cfg)
    raw = fz.to_bytes(comp, cfg, entropy="auto")
    assert not struct.unpack_from("<H", raw, 6)[0] & fz.FLAG_ENTROPY
    assert jnp.array_equal(fz.decompress_bytes(raw), fz.decompress(comp, cfg))


def test_auto_probe_selects_on_field_and_shrinks():
    f = _field(shape=(32, 32, 16))
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel")
    comp = fz.compress(f, cfg)
    plain = fz.to_bytes(comp, cfg, entropy=False)
    auto = fz.to_bytes(comp, cfg, entropy="auto")
    assert struct.unpack_from("<H", auto, 6)[0] & fz.FLAG_ENTROPY
    assert len(auto) < len(plain)


def test_bf16_dtype_accounting_survives_serialization():
    f = _field().astype(jnp.bfloat16)
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel")
    comp = fz.compress(f, cfg)
    back, _ = fz.from_bytes(fz.to_bytes(comp, cfg))
    assert back.dtype_name == "bfloat16"
    assert int(back.raw_bytes()) == f.size * 2


def test_future_version_raises():
    f = _field(shape=(16, 16))
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel")
    raw = bytearray(fz.to_bytes(fz.compress(f, cfg), cfg))
    struct.pack_into("<H", raw, 4, fz.CONTAINER_VERSION + 1)
    with pytest.raises(fz.FZFormatError, match="not supported"):
        fz.from_bytes(bytes(raw))


@pytest.mark.parametrize("junk", [b"", b"abc", b"\x00" * 64, b"FZGC"])
def test_garbage_raises(junk):
    with pytest.raises(fz.FZFormatError):
        fz.from_bytes(junk)


def test_truncated_container_raises():
    f = _field(shape=(16, 16))
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel")
    raw = fz.to_bytes(fz.compress(f, cfg), cfg)
    with pytest.raises(fz.FZFormatError, match="truncated"):
        fz.from_bytes(raw[: len(raw) // 2])


def test_frozen_v1_fixtures_decode_bit_exactly():
    expected = np.load(DATA / "expected_v1.npy")
    for name in ("container_v1_plain.bin", "container_v1_entropy.bin"):
        raw = (DATA / name).read_bytes()
        rec = np.asarray(fz.decompress_bytes(raw))
        assert np.array_equal(rec, expected), name
    plain = (DATA / "container_v1_plain.bin").read_bytes()
    entro = (DATA / "container_v1_entropy.bin").read_bytes()
    assert not struct.unpack_from("<H", plain, 6)[0] & fz.FLAG_ENTROPY
    assert struct.unpack_from("<H", entro, 6)[0] & fz.FLAG_ENTROPY


def test_frozen_legacy_stream_decodes_bit_exactly():
    raw = (DATA / "legacy_stream.bin").read_bytes()
    expected = np.load(DATA / "expected_legacy.npy")
    c, cfg = fz.from_bytes(raw)
    assert cfg.exact_outliers and c.dtype_name == "float32"
    assert np.array_equal(np.asarray(fz.decompress(c, cfg)), expected)


def test_pool_cold_entropy_parity():
    """A cold_entropy pool must gather bit-identically to a plain pool: the
    blob tier may change storage, never numerics."""
    rng = np.random.default_rng(1)
    L, kvh, d, S = 1, 2, 16, 24
    k = jnp.asarray(rng.standard_normal((L, 1, 32, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, 1, 32, kvh, d)), jnp.float32)
    gathers = {}
    for cold_entropy in (False, True):
        cfg = PoolConfig(num_pages=4, page_size=8, seq_capacity=32,
                         cold_after=1, eb=1e-4, cold_entropy=cold_entropy)
        pool = PagePool(cfg, n_layers=L, n_kv_heads=kvh, head_dim=d)
        assert pool.write_prefill(0, k, v, S, step=0)
        pool.compress_pages([p.page_id for p in pool.pages_of(0)])
        out = pool.gather([0])
        gathers[cold_entropy] = (np.asarray(out["k"]), np.asarray(out["v"]))
        blob_pages = [p for p in pool.pages.values() if p.blob is not None]
        assert (len(blob_pages) > 0) is cold_entropy
    assert np.array_equal(gathers[False][0], gathers[True][0])
    assert np.array_equal(gathers[False][1], gathers[True][1])
