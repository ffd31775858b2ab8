"""The benchmark's harness: files found by name, generators, reference,
the entry point's refusal off a TPU, and the compile cache."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import fields, harness, reference

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run(root, cell, trace=False, seed=2**33 + 7, seconds=0.3):
    return harness.run(harness.load_cell(cell, root), seed, seconds, trace,
                       t_start=0.0, require_tpu=False, log=lambda msg: None)


def test_every_cell_has_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        if cell.config.get("kind", "field") == "train":
            assert cell.config["mesh"] and cell.traffic["grad_compress"]
            assert {"seq_len", "global_batch", "limits"} <= set(cell.traffic)
        else:
            assert cell.config["shape"] and cell.config["variables"]
            assert {"eb", "eb_mode", "exact_outliers"} <= set(cell.traffic)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(ROOT, m))
        assert "setup_s" in cell.end_to_end


def test_unknown_cell_is_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell("no-such-cell")


def test_drop_in_config_traffic_and_metric(tiny_root, no_cache):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, with entries in BENCHMARK.json and no edit to any existing file,
    make a cell that runs."""
    (tiny_root / "bench" / "configs" / "flat-1d.json").write_text(json.dumps({
        "shape": [20000], "dtype": "float32", "check_calls": 1,
        "variables": [{"name": "a", "kind": "smooth",
                       "params": {"structure_seed": 1}},
                      {"name": "b", "kind": "turbulent",
                       "params": {"structure_seed": 2}}]}))
    (tiny_root / "bench" / "traffic" / "paper-eb1e-2.json").write_text(json.dumps(
        {"eb": 1e-2, "eb_mode": "rel", "exact_outliers": False}))
    (tiny_root / "bench" / "metrics" / "calls_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.trace.calls['compress'])\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "flat-1d", "source": "a test",
                            "file": "bench/configs/flat-1d.json", "reduced": [],
                            "why": "a test"})
    spec["workloads"].append({"name": "flat-1d.paper2", "config": "flat-1d",
                              "traffic": "paper-eb1e-2", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "calls_traced", "unit": "calls",
                              "better": "higher", "source": "program_counter",
                              "layer": "device", "moves": "compress_gbps",
                              "workloads": ["flat-1d.paper2"]})
    for m in spec["end_to_end"]:          # the field cells' metrics
        if "nyx-512.strict" in m.get("workloads", ["nyx-512.strict"]):
            m.setdefault("workloads", []).append("flat-1d.paper2")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    out = _run(tiny_root, "flat-1d.paper2", trace=True)
    assert out["correct"] is True
    assert out["metrics"]["calls_traced"]["value"] >= 1
    assert out["metrics"]["calls_traced"]["unit"] == "calls"
    out = _run(tiny_root, "flat-1d.paper2")
    assert set(out["metrics"]) == {"compress_gbps", "decompress_gbps", "ratio",
                                   "workspace_gib", "setup_s"}
    assert list(out)[-1] == "checks"
    assert no_cache, "the harness did not turn the compile cache on"


@pytest.mark.parametrize("cell", ["nyx-512.strict", "hurricane-isabel.paper"])
def test_tiny_run_on_cpu(tiny_root, no_cache, cell):
    out = _run(tiny_root, cell)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compress_gbps"] > 0 and m["decompress_gbps"] > 0
    assert m["ratio"] > 1 and m["workspace_gib"] > 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert out["checks"]["mismatch"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("variable", [
    {"kind": "turbulent", "params": {"slope": 11 / 12, "noise": 0.01,
                                     "structure_seed": 2015}},
    {"kind": "smooth", "params": {"structure_seed": 2003, "noise": 0.01}},
])
def test_generators_are_seeded(variable):
    shape = (6, 16, 40)
    a = fields.make(variable, shape, 2**33 + 1, 0)
    b = fields.make(variable, shape, 2**33 + 1, 0)
    c = fields.make(variable, shape, 2**33 + 2, 0)
    d = fields.make(variable, shape, 2**33 + 1, 1)
    assert a.shape == shape and a.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(a)))
    assert bool(jnp.array_equal(a, b))
    assert not bool(jnp.array_equal(a, c)) and not bool(jnp.array_equal(a, d))
    assert float(jnp.std(a)) > 0
    # the seed draws only the noise: the fields stay close
    assert float(jnp.std(a - c)) < 0.05 * float(jnp.std(a))


def test_unknown_field_kind():
    with pytest.raises(ValueError):
        fields.make({"kind": "hacc", "params": {"structure_seed": 1}}, (8,), 0, 0)


def test_reference_bound_is_the_programs():
    """The reference's eb_abs is the one the compressor states (checked
    against the program here; the reference itself imports none of it)."""
    from repro.core import fz
    rng = np.random.default_rng(0)
    for scale, shift in ((1.0, 0.0), (3e-4, 7.0), (250.0, -1e3)):
        x = (rng.standard_normal(5000) * scale + shift).astype(np.float32)
        for mode, eb in (("rel", 1e-3), ("abs", 0.37)):
            want = fz.resolve_eb(jnp.asarray(x), fz.FZConfig(eb=eb, eb_mode=mode))
            assert reference.bound(x, eb, mode) == np.float32(want)


def test_reference_compare():
    x = np.array([0.0, 0.3, 0.5, -0.5, 1.49, 2.5, -7.0], np.float32)
    eb = reference.bound(x, 0.5, "abs")               # 0.5: grid of 1.0
    good = np.array([0, 0, 0, 0, 1, 2, -7], np.float32)
    ties = np.array([0, 0, 1, -1, 1, 3, -7], np.float32)  # the other neighbour
    assert eb == np.float32(0.5)
    assert reference.compare(x, good, 0.5, "abs") == {"mismatch": 0, "max_err_over_eb": 1.0}
    assert reference.compare(x, ties, 0.5, "abs")["mismatch"] == 0
    off_grid = good.copy()
    off_grid[1] = 0.25
    far = good.copy()
    far[4] = 2.0
    assert reference.compare(x, off_grid, 0.5, "abs")["mismatch"] == 1
    out = reference.compare(x, far, 0.5, "abs")
    assert out["mismatch"] == 1 and out["max_err_over_eb"] > 1
    assert reference.compare(x, good[:3], 0.5, "abs")["mismatch"] == x.size
    assert not reference.verdict({"mismatch": 0, "max_err_over_eb": float("nan"),
                                  "truncated_mismatch": 0})


def test_control_breaks_the_bound():
    x = (np.random.default_rng(1).standard_normal(20000) * 50).astype(np.float32)
    out = reference.compare(x, reference.control(x, 1e-3, "rel"), 1e-3, "rel")
    assert not reference.verdict({**out, "truncated_mismatch": 0})


def _cli(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "nyx-512.strict", "--seed", "1", "--seconds", "1",
                           "--trace", "0"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_tpu():
    p = _cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "refused" in p.stderr


def test_cli_fails_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    directories has no system to measure."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""


def test_compile_cache_inside_the_checkout(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert pathlib.Path(compile_cache.cache_dir()) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_workspace_is_the_larger_temp(tiny_root, no_cache):
    cell = harness.load_cell("hurricane-isabel.strict", tiny_root)
    cfg = harness.fz_config(cell.traffic)
    x = jax.ShapeDtypeStruct(tuple(cell.config["shape"]), jnp.float32)
    comp, dec = harness.programs(x, cfg)
    want = max(comp.memory_analysis().temp_size_in_bytes,
               dec.memory_analysis().temp_size_in_bytes)
    out = _run(tiny_root, "hurricane-isabel.strict")
    assert out["metrics"]["workspace_gib"]["value"] == want / 2**30
