"""Ahead-of-time compiles of the main path's kernels for a described v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, which finds what Mosaic refuses (block tiling, VMEM,
unimplemented primitives) without chip time. Nothing runs, so these say
nothing about results or speed. Each test asserts the compiled program holds
a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time,
so every test worker collects the same tests and only the worker given this
file loads the TPU library. The code's own backend checks still see the CPU;
the ``on_tpu`` fixture steers them to the chip's branch.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fz
from repro.kernels import bitshuffle_flag as bsf
from repro.kernels import flash_decode as fdk
from repro.kernels import fused_compress as fc
from repro.kernels import fused_decode as fdd
from repro.kernels import lorenzo_quant as lq
from repro.kernels import ops
from repro.tune import dispatch

KERNEL_MARK = "tpu_custom_call"
NYX, ISABEL, CESM = (512, 512, 512), (100, 500, 500), (1800, 3600)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "backend_interpret", lambda: False)
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")


def _arg(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("residual", [False, True], ids=["paper", "strict"])
@pytest.mark.parametrize("shape", [NYX, ISABEL, CESM, (1 << 24,)],
                         ids=["nyx", "isabel", "cesm", "1d"])
def test_lorenzo_quant_compiles(one_chip, shape, residual):
    fn = lambda x, eb: lq.lorenzo_quant(x, eb, with_residual=residual)
    text = _compiled_text(fn, _arg(one_chip, shape, jnp.float32),
                          _arg(one_chip, (), jnp.float32))
    assert KERNEL_MARK in text


@pytest.mark.parametrize("direction", ["shuffle", "unshuffle"])
def test_bitshuffle_compiles_on_nyx_stream(one_chip, direction):
    n_tiles = 512 ** 3 // bsf.TILE
    fn, shape = {
        "shuffle": (bsf.bitshuffle_flag, (n_tiles, bsf.TILE)),
        "unshuffle": (bsf.bitunshuffle_tiles,
                      (bsf.BLOCK_WORDS, n_tiles, bsf.BLOCKS_PER_TILE)),
    }[direction]
    text = _compiled_text(fn, _arg(one_chip, shape, jnp.uint16))
    assert KERNEL_MARK in text


@pytest.mark.parametrize("shape, strict", [
    pytest.param(ISABEL, False, id="paper"), pytest.param(ISABEL, True, id="strict"),
    pytest.param(CESM, False, id="cesm-paper"), pytest.param(CESM, True, id="cesm-strict")])
def test_staged_pipeline_compiles(one_chip, on_tpu, shape, strict):
    """The whole jitted compress and decompress the public wrappers run at
    100x500x500 and at 1800x3600, as dispatch resolves them on a TPU. A 2D
    field reaches ``lorenzo_quant`` as itself, its rows padded to whole
    bands, and not as a 3D or flattened view."""
    cfg = fz.FZConfig(eb=1e-3, eb_mode="rel", use_kernels=True,
                      exact_outliers=strict)
    n = math.prod(shape)
    c_cfg = fz._resolved(cfg, "compress", n, "float32")
    d_cfg = fz._resolved(cfg, "decompress", n, "float32")
    assert (c_cfg.kernel_mode, d_cfg.kernel_mode) == ("staged", "staged")
    x = _arg(one_chip, shape, jnp.float32)
    comp = fz._compress_jit.lower(x, c_cfg).compile()
    assert KERNEL_MARK in comp.as_text()
    if len(shape) == 2:
        rows = -(-shape[0] // lq.ROWS_BAND) * lq.ROWS_BAND
        calls = [line for line in comp.as_text().splitlines()
                 if line.lstrip().startswith("%lorenzo_quant") and KERNEL_MARK in line]
        assert len(calls) == 1 and f"f32[{rows},{shape[1]}]" in calls[0]
    c_abs = jax.eval_shape(lambda d: fz._compress_jit(d, c_cfg), x)
    c_abs = jax.tree.map(lambda a: _arg(one_chip, a.shape, a.dtype), c_abs)
    dec = fz._decompress_jit.lower(c_abs, d_cfg).compile()
    assert KERNEL_MARK in dec.as_text()


def test_fused_routing_limit_rests_on_the_compiler(one_chip):
    """``TPU_FUSED_MAX_ELEMS`` is 0 because Mosaic refuses both fused
    megakernels; once one compiles, the limit may rise to where it does."""
    limit = dispatch.TPU_FUSED_MAX_ELEMS
    n = max(limit, bsf.TILE)
    cap = fz.FZConfig().payload_capacity(n)
    plan = fc.plan_stream((n,))
    compress = lambda x, eb: fc.fused_compress(x, eb, capacity=cap)
    decode = lambda b, p, eb: fdd.fused_decompress(b, p, eb, shape=(n,))
    args_c = (_arg(one_chip, (n,), jnp.float32), _arg(one_chip, (), jnp.float32))
    args_d = (_arg(one_chip, (plan.flag_words,), jnp.uint32),
              _arg(one_chip, (cap, 8), jnp.uint16), _arg(one_chip, (), jnp.float32))
    if limit == 0:
        for fn, args in ((compress, args_c), (decode, args_d)):
            with pytest.raises(Exception, match="Pallas TPU lowering|Mosaic|VMEM"):
                _compiled_text(fn, *args)
    else:
        assert KERNEL_MARK in _compiled_text(compress, *args_c)
        assert KERNEL_MARK in _compiled_text(decode, *args_d)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_flash_decode_compiles_at_glm4_9b_decode(one_chip, layout):
    """B=8, S=4096, 32 query heads over 2 KV heads of 128, bf16."""
    B, S, H, KVH, D = 8, 4096, 32, 2, 128
    q = _arg(one_chip, (B, H, D), jnp.bfloat16)
    length = _arg(one_chip, (B,), jnp.int32)
    if layout == "contiguous":
        kv = _arg(one_chip, (B, S, KVH, D), jnp.bfloat16)
        fn = lambda q, k, v, n: fdk.flash_decode(q, k, v, n, interpret=False)
    else:
        kv = _arg(one_chip, (B, S // 128, 128, KVH, D), jnp.bfloat16)
        fn = lambda q, k, v, n: fdk.combine_partials(
            *fdk.decode_partials_pages(q, k, v, n, interpret=False))
    assert KERNEL_MARK in _compiled_text(fn, q, kv, kv, length)
