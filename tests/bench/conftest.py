"""Shared fixtures of the benchmark's tests.

``tiny_root`` is a checkout of the benchmark alone (``BENCHMARK.json`` and
``bench/``) whose configurations are cut to a few small fields, and whose
training configurations are cut to the program's smoke widths and a short
sequence, so a whole run goes through the harness on the CPU, with the
Pallas kernels interpreted. ``no_cache`` keeps those runs out of the persistent compile
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
# a training configuration at the program's smoke widths, its traffic short
TINY_TRAIN = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
              "num_key_value_heads": 2, "vocab_size": 512}
TINY_TRAIN_TRAFFIC = {"seq_len": 64, "min_steps": 2, "min_traced_steps": 2}


def make_tiny_root(root: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark under ``root`` with every configuration cut."""
    shutil.copytree(ROOT / "bench", root / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for path in (root / "bench" / "configs").glob("*.json"):
        conf = json.loads(path.read_text())
        if conf.get("kind", "field") == "train":
            conf.update(TINY_TRAIN)
        else:
            conf["shape"] = TINY_SHAPE
            conf["variables"] = conf["variables"][:3]
        path.write_text(json.dumps(conf))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        conf = json.loads((root / next(c["file"] for c in spec["configs"]
                                       if c["name"] == w["config"])).read_text())
        if conf.get("kind", "field") == "train":
            path = root / "bench" / "traffic" / f"{w['traffic']}.json"
            path.write_text(json.dumps({**json.loads(path.read_text()), **TINY_TRAIN_TRAFFIC}))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


@pytest.fixture(scope="session")
def tiny_root_maker():
    """``make_tiny_root``, for fixtures that outlive one test."""
    return make_tiny_root


@pytest.fixture
def no_cache(monkeypatch):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache, "use_compile_cache",
                        lambda: calls.append(1) or compile_cache.cache_dir())
    return calls
