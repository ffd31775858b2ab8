"""Shared fixtures of the benchmark's tests.

``tiny_root`` is a checkout of the benchmark alone (``BENCHMARK.json`` and
``bench/``) whose configurations are cut to a few small fields, so a whole
run goes through the harness on the CPU, with the Pallas kernels
interpreted. ``no_cache`` keeps those runs out of the persistent compile
cache and records that the harness asked for it.
"""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_SHAPE = [4, 32, 128]


@pytest.fixture
def tiny_root(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in (tmp_path / "bench" / "configs").glob("*.json"):
        conf = json.loads(path.read_text())
        conf["shape"] = TINY_SHAPE
        conf["variables"] = conf["variables"][:3]
        path.write_text(json.dumps(conf))
    return tmp_path


@pytest.fixture
def no_cache(monkeypatch):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache, "use_compile_cache",
                        lambda: calls.append(1) or compile_cache.cache_dir())
    return calls
