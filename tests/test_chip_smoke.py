"""Rehearsals of ``chip_smoke.py`` off the chip, and the compile-cache rule.

The smoke script's field phase runs here at a tiny shape with the kernels in
interpret mode, so its control flow and checks are exercised without a chip.
Only the presence of a Mosaic kernel in the compiled program cannot be seen
off a TPU; the test steers that one check. The four-chip phase runs at smoke
widths on four CPU devices, in a child process that asks for them.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_field_phase_rehearsal_on_cpu(monkeypatch):
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "_has_kernel", lambda text: True)
    lines = []
    facts = smoke.run_field("tiny", "smooth", (16, 20, 24), seed=0,
                            log=lines.append)
    assert set(facts) == {"paper", "strict"}
    for mode in facts.values():
        assert mode["impl"] == ("staged", "staged")
        assert mode["identical"] == {"container": True, "reconstruction": True}
        assert mode["ratio"] > 1
    assert any("smoke timing, not a benchmark" in s for s in lines)


def test_field_phase_refuses_the_reference(monkeypatch):
    """A dispatch that picks the jnp reference fails the phase."""
    from repro.core import fz
    smoke = _load_smoke()
    monkeypatch.setattr(fz, "_resolved",
                        lambda cfg, *a: fz.FZConfig(eb=cfg.eb, use_kernels=False))
    try:
        smoke.run_field("tiny", "smooth", (8, 8, 8), seed=0, log=lambda s: None)
    except RuntimeError as e:
        assert "jnp reference" in str(e)
    else:
        raise AssertionError("run_field accepted the jnp reference")


FOUR_CHIPS = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
from repro import configs
get = configs.get
configs.get = lambda arch, smoke=False: get(arch, smoke=True)
import chip_smoke as cs
cs.FOUR_CHIP = dict(cs.FOUR_CHIP, seq=64)
losses = cs.run_four_chips(log=print)
assert set(losses) == {"compressed", "plain"}, losses
print("REHEARSAL OK")

# per-pod activations spread over both pods (no spmd_axis_name, 'pod' left
# to the act sharder): each per-pod gradient is all-reduced across pods in
# full before its compressed hop, which the step check must refuse
from repro.dist import compressed_allreduce as car
from repro.dist import sharding as shd
from repro.launch.train import build_trainer
resolve, vmap = shd.resolve_spec, jax.vmap
shd.resolve_spec = lambda logical, shape, mesh, exclude=(): resolve(logical, shape, mesh)
jax.vmap = lambda f, *a, spmd_axis_name=None, **k: vmap(f, *a, **k)
gcfg = car.GradCompressionConfig(enabled=True, use_kernels=True)
trainer, _ = build_trainer("yi-6b", layers=1, seq=64, batch=8, steps=1,
                           pods=2, model_parallel=2, grad_compress=gcfg)
failed = cs._check_step("compressed", trainer, gcfg, 2, log=print)
assert any("all-reduce bytes across pods" in f for f in failed), failed
print("MUTANT REFUSED")
"""


def test_four_chip_phase_rehearsal_on_cpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(ROOT / "src"),
                        str(ROOT)], env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    assert "REHEARSAL OK" in r.stdout and "MUTANT REFUSED" in r.stdout
    assert "relative loss difference compressed vs plain" in r.stdout


def _run_script(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    r = _run_script(ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_exits_nonzero_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run_script(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_honours_the_env_var(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the code sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        assert compile_cache.use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
