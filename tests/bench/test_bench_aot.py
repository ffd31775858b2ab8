"""Each cell's compress and decompress programs compile at the cell's real
shapes for a described v5e, through the public API as the harness compiles
them, with no chip attached.

The topology is described in a module fixture, never at import time, so
every test worker collects the same tests and only the worker given this
file loads the TPU library. The program's own backend checks still see the
CPU; the ``on_tpu`` fixture steers them to the chip's branch.
"""
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness, roofline

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KERNELS = {"lorenzo_quant", "bitshuffle_flag", "bitunshuffle_tiles"}
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    from repro.kernels import ops
    from repro.tune import dispatch
    monkeypatch.setattr(ops, "backend_interpret", lambda: False)
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_programs_compile_for_v5e(one_chip, on_tpu, cell):
    c = harness.load_cell(cell)
    x = jax.ShapeDtypeStruct(tuple(c.config["shape"]), jnp.float32, sharding=one_chip)
    comp, dec = harness.programs(x, harness.fz_config(c.traffic))
    found = set(roofline.kernel_bytes(comp.as_text())) | \
        set(roofline.kernel_bytes(dec.as_text()))
    assert found == KERNELS
    resident = len(c.config["variables"]) * x.size * 4 * 1.5   # fields + containers
    for prog in (comp, dec):
        temp = prog.memory_analysis().temp_size_in_bytes
        assert 0 < temp and resident + temp < HBM_BYTES
