"""The trace reduction and the roofline byte counts of the benchmark."""
import pathlib

import pytest

from bench import roofline, xplane

DATA = pathlib.Path(__file__).parent / "data"
E = xplane.Event


def test_union_merges_overlaps():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.union([]) == []


@pytest.mark.parametrize("name, label, base", [
    ("%lorenzo_quant.1 = (u16[8,64,512]{2,1,0}, s32[8,64,512]{2,1,0}) custom-call(%a)",
     "lorenzo_quant.1 u16[8,64,512]", "lorenzo_quant"),
    ("%fusion = u16[3125248,8]{0,1:T(8,128)} fusion(%b), kind=kCustom",
     "fusion u16[3125248,8]", "fusion"),
    ("%while.3 = (u32[]{:T(128)}, s32[25]{0}) while(%t)", "while.3 u32[]", "while"),
    ("bench.compress", "bench.compress", "bench.compress"),
])
def test_op_labels(name, label, base):
    assert xplane.op_label(name) == label
    assert xplane.kernel_base(label) == base


def _hand_made():
    """Two calls on one chip: compress [0, 10] runs ops [1, 3] and [4, 8];
    decompress [10, 16] runs [11, 13]. Device times are shifted by -5 (the
    device clock is not the host's) and must not matter."""
    host = [E("bench.compress", 0, 10), E("bench.decompress", 10, 16),
            E("fz.compress", 0.5, 9.5)]
    modules = [E("jit_a", -4, 3), E("jit_b", 6, 8.5)]
    ops = [E("%k.1 = f32[4]{0} custom-call(%x)", -4, -2),
           E("%fusion.2 = f32[4]{0} fusion(%x)", -1, 3),
           E("%fusion.2 = f32[4]{0} fusion(%y)", 6, 8)]
    return {"/device:TPU:0": (modules, ops)}, host


def test_reduce_hand_made():
    r = xplane.reduce(*_hand_made())
    assert r.calls == {"compress": 1, "decompress": 1}
    assert r.span_s == {"compress": 10, "decompress": 6}
    assert r.busy_s == {"compress": 6, "decompress": 2}
    assert r.busy_total_s == 8
    assert r.window_s == 16
    assert r.op_s["compress"] == {"k.1 f32[4]": 2, "fusion.2 f32[4]": 4}
    assert r.op_n["decompress"] == {"fusion.2 f32[4]": 1}
    assert r.gaps == [("host between calls: compress -> decompress", 3),
                      ("inside compress", 1)]


def test_reduce_names_modules_when_counts_differ():
    devices, host = _hand_made()
    modules, ops = devices["/device:TPU:0"]
    named = [E("jit__compress_jit(1)", -4, 3), E("jit__decompress_jit(2)", 6, 8.5)]
    extra = host + [E("bench.compress", 20, 21)]
    r = xplane.reduce({"/device:TPU:0": (named, ops)}, extra)
    assert r.busy_s == {"compress": 6, "decompress": 2}
    assert r.calls == {"compress": 2, "decompress": 1}


def test_reduce_needs_the_spans():
    with pytest.raises(RuntimeError):
        xplane.reduce({}, [E("fz.compress", 0, 1)])


def test_reduce_recorded_trace():
    """A trace recorded on a v5e: 6 compress and 6 decompress calls of a
    4x64x512 strict-mode field through the harness's window."""
    devices, host = xplane.read(str(DATA / "tiny.xplane.pb"))
    assert list(devices) == ["/device:TPU:0"]
    r = xplane.reduce(devices, host)
    assert r.calls == {"compress": 6, "decompress": 6}
    for d in ("compress", "decompress"):
        assert 0 < r.busy_s[d] < r.span_s[d]
    assert r.busy_total_s == pytest.approx(sum(r.busy_s.values()))
    assert 0 < r.busy_total_s < r.window_s
    bases = {d: {xplane.kernel_base(k) for k in r.op_s[d]} for d in r.op_s}
    assert {"lorenzo_quant", "bitshuffle_flag"} <= bases["compress"]
    assert "bitunshuffle_tiles" in bases["decompress"]
    assert not {"lorenzo_quant", "bitshuffle_flag"} & bases["decompress"]
    assert all(n == 6 for label, n in r.op_n["compress"].items()
               if xplane.kernel_base(label) == "lorenzo_quant")
    assert len(r.gaps) == 10 and all(s > 0 for _, s in r.gaps)
    assert [s for _, s in r.gaps] == sorted((s for _, s in r.gaps), reverse=True)


def test_kernel_bytes_from_recorded_calls():
    """Operand and result bytes of each kernel call, worked out by hand
    from the shapes; the field passed twice to lorenzo_quant counts once."""
    got = roofline.kernel_bytes((DATA / "tiny_kernels.hlo.txt").read_text())
    n = 8 * 64 * 512
    assert got == {
        "lorenzo_quant": [3 * 4 + n * 4 + n * 2 + n * 4],
        "bitshuffle_flag": [32 * 4096 * 2 + 8 * 32 * 512 * 2 + 32 * 512],
        "bitunshuffle_tiles": [8 * 32 * 512 * 2 + 32 * 4096 * 2],
    }


def test_shape_bytes():
    assert roofline.shape_bytes("(f32[3]{0}, u16[2,5]{1,0:T(8,128)}, pred[])") == 12 + 20 + 1


def test_least_bytes():
    assert roofline.least_bytes("compress", 100, 7) == 207
    assert roofline.least_bytes("decompress", 100, 7) == 107
    with pytest.raises(ValueError):
        roofline.least_bytes("both", 1, 1)
