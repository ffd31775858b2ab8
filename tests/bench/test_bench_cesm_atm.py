"""The ``cesm-atm.strict`` cell at its own dimensionality.

``tiny_root`` cuts every field configuration to a small 3D shape, which
would never run the 2D path, so these tests cut ``cesm-atm.json`` again to
a small 2D field: its row count is not a multiple of the Lorenzo kernel's
``ROWS_BAND`` and its width is not a multiple of the 128-lane tile. The
cell then runs through the harness on the CPU with the kernels
interpreted. That the compiled program hands the quantization kernel the
2D field itself is checked at 1800x3600 in ``tests/test_tpu_compile.py``.
"""
import json

from bench import harness
from repro.kernels import lorenzo_quant as lq

CELL = "cesm-atm.strict"
SHAPE = (40, 360)           # 40 rows: 2.5 bands of 16; 360: not lane-aligned
VARIABLES = 3


def _cut(root) -> harness.Cell:
    """The cell under ``root`` with its configuration cut to ``SHAPE``."""
    path = root / "bench" / "configs" / "cesm-atm.json"
    conf = json.loads(path.read_text())
    conf["shape"] = list(SHAPE)
    conf["variables"] = conf["variables"][:VARIABLES]
    path.write_text(json.dumps(conf))
    return harness.load_cell(CELL, root)


def test_published_shape_is_2d():
    conf = harness.load_cell(CELL).config
    assert conf["shape"] == [1800, 3600] and conf["dtype"] == "float32"
    assert len(conf["variables"]) == 79 and conf["reduced"] == []
    assert {v["kind"] for v in conf["variables"]} == {"smooth"}


def test_cesm_atm_runs_2d_on_cpu(tiny_root, no_cache):
    cell = _cut(tiny_root)
    assert SHAPE[0] % lq.ROWS_BAND and SHAPE[1] % 128
    out = harness.run(cell, 2**33 + 17, 0.3, False, t_start=0.0,
                      require_tpu=False, log=lambda msg: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2 * VARIABLES
    assert out["checks"]["mismatch"] == {"value": 0, "limit": 0}
    assert out["checks"]["truncated_mismatch"]["value"] == 0
    assert out["checks"]["max_err_over_eb"]["value"] <= 1
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"compress_gbps", "decompress_gbps", "ratio",
                      "workspace_gib", "setup_s"}
    assert m["ratio"] > 1 and m["workspace_gib"] > 0

