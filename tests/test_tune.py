"""repro.tune: registry/tuner/cache/dispatch contracts.

Pins the satellite checklist for the autotuner PR: cache persistence
round-trip, shape-bucket collapsing, schema-version invalidation,
corrupted/truncated-file recovery, the parity gate rejecting a seeded
wrong-output candidate (and never selecting it), the backend-aware
fallback ordering, the analysis budget skip, and the end-to-end
``kernel_mode="auto"`` path staying bit-identical to the reference.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core import fz
from repro.tune import cache as tcache
from repro.tune import dispatch, impls, registry, tuner


@pytest.fixture
def tmp_cache(tmp_path):
    """Point the process-wide dispatch cache at a throwaway file."""
    tc = dispatch.configure(tmp_path / "tune_cache.json")
    yield tc
    dispatch.reset()


def _fake_op(name, impls=("ref", "fast")):
    """Register a trivial op (instant candidates, bit-identity gate)."""
    def make_context(*, n, dtype):
        return {"n": n, "dtype": dtype,
                "x": jnp.arange(16, dtype=jnp.float32)}

    def parity(ctx, out, ref_out):
        if np.array_equal(np.asarray(out), np.asarray(ref_out)):
            return None
        return "mismatch"

    registry.register_op(registry.OpSpec(
        name=name, reference="ref", make_context=make_context,
        parity=parity, gate="bit-identity"))
    for impl in impls:
        def make_runner(ctx, impl=impl):
            return lambda: ctx["x"] * 2.0
        registry.register(registry.Candidate(
            op=name, impl=impl, make_runner=make_runner))


@pytest.fixture
def fake_op():
    name = "test.fake"
    _fake_op(name)
    yield name
    registry._OPS.pop(name, None)
    registry._CANDS.pop(name, None)


def test_shape_bucket_powers_of_two():
    assert tcache.shape_bucket(1) == 1
    assert tcache.shape_bucket(4096) == 4096
    assert tcache.shape_bucket(4097) == 8192
    assert tcache.shape_bucket(50_000) == 65_536
    key = tcache.cache_key("interpret", "fz.compress", 50_000, "float32", "cpu")
    assert "pow2:65536" in key


def test_cache_roundtrip_persistence(tmp_path):
    path = tmp_path / "tc.json"
    tc = tcache.TuneCache(path).load()
    assert tc.status == "missing" and len(tc) == 0
    key = tcache.cache_key("interpret", "op", 4096, "float32", "cpu")
    tc.put(key, {"impl": "staged", "measured_us": {"staged": 1.0}})
    tc.save()
    tc2 = tcache.TuneCache(path).load()
    assert tc2.status == "ok"
    assert tc2.get(key)["impl"] == "staged"


def test_cache_schema_bump_invalidates(tmp_path):
    path = tmp_path / "tc.json"
    doc = {"schema": tcache.SCHEMA_VERSION + 1,
           "entries": {"k": {"impl": "fused"}}}
    path.write_text(json.dumps(doc))
    tc = tcache.TuneCache(path).load()
    assert tc.status == "schema-mismatch" and len(tc) == 0


@pytest.mark.parametrize("blob", [b"{not json", b"", b"[1,2,3]", b"\x00\xff"])
def test_cache_corrupt_file_recovers(tmp_path, blob):
    path = tmp_path / "tc.json"
    path.write_bytes(blob)
    tc = tcache.TuneCache(path).load()
    assert len(tc) == 0          # never raises, loads empty
    tc.put("k", {"impl": "staged"})
    tc.save()                    # rewrites a clean file
    assert tcache.TuneCache(path).load().status == "ok"


def test_truncated_cache_retunes_cleanly(tmp_path, fake_op):
    path = tmp_path / "tc.json"
    tc = dispatch.configure(path)
    try:
        entry, measured = tuner.tune_op(fake_op, n=64, dtype="float32",
                                        cache=tc, k=1, warmup=0, log=lambda *a: None)
        assert measured and entry["impl"] in ("ref", "fast")
        # truncate the file mid-stream, then reload: the tuner must measure
        # again (clean retune) and write a valid file back
        path.write_bytes(path.read_bytes()[: path.stat().st_size // 2])
        tc = dispatch.configure(path)
        entry2, measured2 = tuner.tune_op(fake_op, n=64, dtype="float32",
                                          cache=tc, k=1, warmup=0, log=lambda *a: None)
        assert measured2
        assert tcache.TuneCache(path).load().status == "ok"
    finally:
        dispatch.reset()


def test_shape_bucket_collapsing(tmp_cache, fake_op):
    _, measured = tuner.tune_op(fake_op, n=3000, dtype="float32",
                                cache=tmp_cache, k=1, warmup=0, log=lambda *a: None)
    assert measured
    # 3000 and 4096 share the pow2:4096 bucket -> pure cache hit
    _, measured2 = tuner.tune_op(fake_op, n=4096, dtype="float32",
                                 cache=tmp_cache, k=1, warmup=0, log=lambda *a: None)
    assert not measured2
    # a different bucket tunes afresh
    _, measured3 = tuner.tune_op(fake_op, n=8192, dtype="float32",
                                 cache=tmp_cache, k=1, warmup=0, log=lambda *a: None)
    assert measured3


def test_second_run_zero_measurements(tmp_cache, fake_op):
    workloads = [(fake_op, 64, "float32"), (fake_op, 256, "float32")]
    s1 = tuner.ensure_tuned(workloads, cache=tmp_cache, k=1, warmup=0,
                            log=lambda *a: None)
    assert s1["misses"] == 2 and s1["measurements"] > 0
    s2 = tuner.ensure_tuned(workloads, cache=tmp_cache, k=1, warmup=0,
                            log=lambda *a: None)
    assert s2["hits"] == 2 and s2["misses"] == 0 and s2["measurements"] == 0


def test_parity_gate_rejects_wrong_candidate(tmp_cache, fake_op):
    def make_runner(ctx):
        return lambda: ctx["x"] * -1.0   # instant, structurally right, wrong
    evil = registry.Candidate(op=fake_op, impl="evil", make_runner=make_runner)
    with registry.scoped(evil):
        entry, _ = tuner.tune_op(fake_op, n=64, dtype="float32",
                                 cache=tmp_cache, k=1, warmup=0, log=lambda *a: None)
    assert entry["impl"] != "evil"
    assert "evil" in entry["rejected"]
    assert "evil" not in entry["measured_us"]   # never even timed


def test_parity_gate_rejects_wrong_fz_decode(tmp_cache):
    """The seeded wrong-output candidate on the *real* fz.decompress op:
    zeroed reconstructions are instant but fail bit-identity — the gate must
    reject them however fast they are."""
    evil = impls.evil_candidate("fz.decompress")
    with registry.scoped(evil):
        entry, _ = tuner.tune_op("fz.decompress", n=4096, dtype="float32",
                                 cache=tmp_cache, k=1, warmup=0,
                                 log=lambda *a: None)
    assert entry["impl"] != "evil"
    assert "bit-identical" in entry["rejected"]["evil"]
    assert entry["gate"] == "bit-identity"


def test_compress_parity_gate_is_error_bound(tmp_cache):
    evil = impls.evil_candidate("fz.compress")
    with registry.scoped(evil):
        entry, _ = tuner.tune_op("fz.compress", n=4096, dtype="float32",
                                 cache=tmp_cache, k=1, warmup=0,
                                 log=lambda *a: None)
    assert entry["impl"] != "evil"
    assert "error bound" in entry["rejected"]["evil"]
    assert entry["gate"] == "error-bound"


def test_fallback_ordering_interpret(tmp_cache):
    """No cache entry: interpret-class backends must prefer staged over
    fused (the measured 4x fused-compress interpreter regression)."""
    assert dispatch.backend() == "interpret"   # CI runs on CPU
    assert dispatch.fz_fallback_mode(4096) == "staged"
    assert tune.resolve_fz("compress", 4096, "float32") == "staged"
    assert tune.resolve_fz("decompress", 4096, "float32") == "staged"
    # untuned decode attention honors the explicit kernel request
    assert tune.decode_attention_impl(4096, "bfloat16") == "kernel"
    # a TPU is routed by the size rule, not by this ordering
    assert "tpu" not in dispatch.FZ_FALLBACK


def test_tpu_dispatch_never_yields_reference(tmp_cache, monkeypatch):
    """On a TPU the size rule decides, whatever the tuning cache says: a
    cached "reference" winner never sends a kernel request to the jnp path."""
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    for direction in ("compress", "decompress"):
        tmp_cache.put(tcache.cache_key("tpu", f"fz.{direction}", 4096,
                                       "float32", dispatch.arch()),
                      {"impl": "reference"})
    tmp_cache.put(tcache.cache_key("tpu", "decode_attention", 4096,
                                   "bfloat16", dispatch.arch()),
                  {"impl": "jnp"})
    dispatch.invalidate_memo()
    assert tune.decode_attention_impl(4096, "bfloat16") == "kernel"
    cfg = fz.FZConfig(eb=1e-3, use_kernels=True, exact_outliers=False)
    for n in (1, 4096, 512 ** 3):
        for direction in ("compress", "decompress"):
            assert tune.resolve_fz(direction, n, "float32") == "staged"
            resolved = fz._resolved(cfg, direction, n, "float32")
            assert resolved.use_kernels and resolved.kernel_mode == "staged"
        assert dispatch.fz_fallback_mode(n) == "staged"


def test_tpu_dispatch_picks_fused_only_under_the_limit(monkeypatch):
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "TPU_FUSED_MAX_ELEMS", 1 << 16)
    for direction in ("compress", "decompress"):
        assert tune.resolve_fz(direction, 1 << 16, "float32") == "fused"
        assert tune.resolve_fz(direction, (1 << 16) + 1, "float32") == "staged"
    assert dispatch.fz_fallback_mode(4096) == "fused"
    assert dispatch.fz_fallback_mode(1 << 20) == "staged"


def test_tpu_dispatch_without_a_kernel_is_an_error(monkeypatch):
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    only_ref = [c for c in registry.candidates("fz.compress")
                if c.impl == "reference"]
    monkeypatch.setattr(registry, "candidates", lambda op, backend=None: only_ref)
    with pytest.raises(RuntimeError, match="never falls back"):
        tune.resolve_fz("compress", 4096, "float32")


def test_cached_winner_overrides_fallback(tmp_cache):
    key = tcache.cache_key(dispatch.backend(), "fz.decompress", 4096,
                           "float32", dispatch.arch())
    tmp_cache.put(key, {"impl": "fused"})
    dispatch.invalidate_memo()
    assert tune.resolve_fz("decompress", 4096, "float32") == "fused"


def test_auto_resolution_in_fzconfig(tmp_cache):
    """kernel_mode="auto" is the default and resolves before jit; the
    resolved config is concrete (never "auto")."""
    cfg = fz.FZConfig(eb=1e-3, use_kernels=True, exact_outliers=False)
    assert cfg.kernel_mode == "auto"
    r = fz._resolved(cfg, "compress", 4096, "float32")
    assert r.kernel_mode in ("staged", "fused")
    # reference winner maps to use_kernels=False
    key = tcache.cache_key(dispatch.backend(), "fz.compress", 4096,
                           "float32", dispatch.arch())
    tmp_cache.put(key, {"impl": "reference"})
    dispatch.invalidate_memo()
    r2 = fz._resolved(cfg, "compress", 4096, "float32")
    assert not r2.use_kernels
    # non-auto and non-kernel configs pass through untouched
    explicit = fz.FZConfig(eb=1e-3, use_kernels=True, kernel_mode="fused",
                           exact_outliers=False)
    assert fz._resolved(explicit, "compress", 4096, "float32") is explicit


def test_auto_path_bit_identical_to_reference(tmp_cache):
    x = jnp.asarray(np.cumsum(
        np.random.default_rng(3).standard_normal(4096).astype(np.float32)) * 0.1)
    ref = fz.FZConfig(eb=1e-3, exact_outliers=False)
    auto = fz.FZConfig(eb=1e-3, use_kernels=True, exact_outliers=False)
    c_ref, c_auto = fz.compress(x, ref), fz.compress(x, auto)
    for a, b in zip(jax.tree.leaves(c_ref), jax.tree.leaves(c_auto)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(fz.decompress(c_ref, ref)),
                          np.asarray(fz.decompress(c_auto, auto)))


def test_budget_skip_vmem_overflow():
    """analysis integration: the fused megakernel candidates overflow the
    modelled VMEM budget at 4M elements (their capacity-sized payload is
    VMEM-resident) and must be skipped, not measured; staged stays
    eligible."""
    ctx = {"n": 1 << 22, "dtype": "float32"}
    cands = {c.impl: c for c in registry.candidates("fz.compress")}
    why = tuner._budget_skip(cands["fused"], ctx)
    assert why is not None and "vmem-overflow" in why
    assert tuner._budget_skip(cands["staged"], ctx) is None
    assert tuner._budget_skip(cands["reference"], ctx) is None
    # small shapes fit: nothing is skipped there
    assert tuner._budget_skip(cands["fused"], {"n": 4096,
                                               "dtype": "float32"}) is None


def test_tuner_records_skips_in_entry(tmp_cache, fake_op):
    cand = registry._CANDS[fake_op]["fast"]
    skipping = registry.Candidate(
        op=fake_op, impl="huge", make_runner=cand.make_runner,
        kernel_specs=lambda ctx: [_overflow_spec()])
    with registry.scoped(skipping):
        entry, _ = tuner.tune_op(fake_op, n=64, dtype="float32",
                                 cache=tmp_cache, k=1, warmup=0,
                                 log=lambda *a: None)
    assert "huge" in entry["skipped"]
    assert "huge" not in entry["measured_us"]


def _overflow_spec():
    import repro.kernels  # noqa: F401  -- registers the spec builders
    from repro.analysis.kernelspec import spec_builders
    return spec_builders()["fused_compress"](shape=(1 << 22,),
                                             dtype="float32",
                                             capacity_frac=1.0)


def test_cli_smoke_json(tmp_path, capsys):
    from repro.tune import __main__ as cli
    cache_path = str(tmp_path / "cli_cache.json")
    try:
        rc = cli.main(["--smoke", "--cache", cache_path, "--json",
                       "--ops", "fz.decompress", "--k", "1", "--warmup", "0"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["misses"] == len(out["results"]) > 0
        rc2 = cli.main(["--smoke", "--cache", cache_path, "--json",
                        "--ops", "fz.decompress", "--k", "1", "--warmup", "0"])
        assert rc2 == 0
        out2 = json.loads(capsys.readouterr().out)
        assert out2["measurements"] == 0 and out2["misses"] == 0
        rc3 = cli.main(["--dump", "--cache", cache_path])
        assert rc3 == 0
        assert "fz.decompress" in capsys.readouterr().out
    finally:
        dispatch.reset()
