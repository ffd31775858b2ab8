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
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
KIND = {c["name"]: json.loads((ROOT / c["file"]).read_text()).get("kind", "field")
        for c in SPEC["configs"]}
CELLS = [w["name"] for w in SPEC["workloads"] if KIND[w["config"]] == "field"]
TRAIN_CELLS = [w["name"] for w in SPEC["workloads"] if KIND[w["config"]] == "train"]
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
    """The chip's branch of the program's backend checks. JAX's trace caches
    are cleared around it: a trace of the same function at the same shape
    made earlier in this process took the CPU's branch (interpreted
    kernels), and would be reused."""
    from repro.kernels import ops
    from repro.tune import dispatch
    monkeypatch.setattr(ops, "backend_interpret", lambda: False)
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


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


@pytest.fixture(scope="module")
def pods_mesh():
    """The training cells' mesh over a described v5e 2x2: two pods of two
    chips on the model axis."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.asarray(topo.devices).reshape(2, 1, 2), ("pod", "data", "model"),
                axis_types=(AxisType.Auto,) * 3)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_step_compiles_for_v5e(pods_mesh, on_tpu, cell):
    """The cell's train step at its published widths, with the compressed
    exchange, compiles for four described chips; each holds its state and
    the step's temporaries in 16 GB, and the containers are all that the
    gradients put across pods."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import train, wire
    from repro.configs.base import ShapeConfig
    from repro.optim import adamw_init
    from repro.train import TrainConfig
    from repro.train.step import build_train_step
    c = harness.load_cell(cell)
    conf, traffic = c.config, c.traffic
    assert dict(pods_mesh.shape) == conf["mesh"]
    model = train.model_of(conf)
    sched = traffic["schedule"]
    tcfg = TrainConfig(peak_lr=sched["peak_lr"], warmup_steps=sched["warmup_steps"],
                       total_steps=sched["total_steps"],
                       microbatches=traffic["microbatches"],
                       grad_compress=train.grad_config(traffic))
    shape = ShapeConfig(cell, traffic["seq_len"], traffic["global_batch"], "train")
    fn, info = build_train_step(model, shape, pods_mesh, tcfg)
    params = model.abstract_params()

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                            tree, shardings)
    err = jax.eval_shape(lambda: info["make_err_state"](params))
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=info["batch"][k])
             for k, v in info["input_structs"].items()}
    compiled = fn.lower(placed(params, info["params"]),
                        placed(jax.eval_shape(adamw_init, params), info["opt"]),
                        placed(err, info["err_shardings"](params)),
                        jax.ShapeDtypeStruct((), jnp.int32,
                                             sharding=NamedSharding(pods_mesh, P())),
                        batch).compile()
    m = compiled.memory_analysis()
    assert 0 < m.temp_size_in_bytes < HBM_BYTES
    assert m.argument_size_in_bytes < HBM_BYTES
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert train.wire_numbers(conf, traffic, text, 2) == dict.fromkeys(wire.LIMITS, 0)
