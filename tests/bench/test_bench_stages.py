"""The map from a compiled program's instructions to ``fz.stage.*`` scopes,
and its join with a traced window's device ops."""
import dataclasses
import pathlib

import pytest

from bench import stages, xplane

DATA = pathlib.Path(__file__).parent / "data"

HAND_MADE = """HloModule jit__decompress_jit, is_scheduled=true

%fused_computation (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  ROOT %negate.1 = f32[4]{0} negate(%p.1), metadata={op_name="jit(_decompress_jit)/fz.stage.dequantize/neg"}
}

%fused_computation.2 (p.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  %constant.2 = f32[] constant(0), metadata={op_name="jit(_decompress_jit)/fz.stage.decode_blocks/broadcast_in_dim"}
  %broadcast.2 = f32[4]{0} broadcast(%constant.2), dimensions={}
  ROOT %add.2 = f32[4]{0} add(%p.2, %broadcast.2)
}

%body (t.1: (u32[], f32[4])) -> (u32[], f32[4]) {
  %t.1 = (u32[], f32[4]{0}) parameter(0)
  %i.1 = u32[] get-tuple-element(%t.1), index=0
  %x.1 = f32[4]{0} get-tuple-element(%t.1), index=1
  %add.1 = f32[4]{0} add(%x.1, %x.1)
  ROOT %tuple.1 = (u32[], f32[4]{0}) tuple(%i.1, %add.1)
}

%cond (t.2: (u32[], f32[4])) -> pred[] {
  %t.2 = (u32[], f32[4]{0}) parameter(0)
  %i.2 = u32[] get-tuple-element(%t.2), index=0
  %c.2 = u32[] constant(3)
  ROOT %compare.2 = pred[] compare(%i.2, %c.2), direction=LT
}

ENTRY %main.9 (a.1: f32[4]) -> (f32[4], s32[0]) {
  %a.1 = f32[4]{0} parameter(0)
  %copy.1 = f32[4]{0} copy(%a.1)
  %fusion = f32[4]{0:T(256)} fusion(%copy.1), kind=kLoop, calls=%fused_computation
  %reduce-window.2 = f32[4]{0} add(%fusion, %fusion), metadata={op_name="reduce_window_sum"}
  %multiply.3 = f32[4]{0} multiply(%reduce-window.2, %fusion), metadata={op_name="jit(_decompress_jit)/fz.stage.decode_blocks/jit(f)/fz.stage.unshuffle/mul"}
  %zero.1 = u32[] constant(0)
  %tuple.2 = (u32[], f32[4]{0}) tuple(%zero.1, %multiply.3)
  %while.1 = (u32[], f32[4]{0}) while(%tuple.2), condition=%cond, body=%body
  %get-tuple-element.5 = f32[4]{0} get-tuple-element(%while.1), index=1
  %fusion.5 = f32[4]{0} fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation.2
  %subtract.6 = f32[4]{0} subtract(%fusion.5, %a.1), metadata={op_name="jit(_decompress_jit)/fz.stage.dequantize/sub"}
  %constant.7 = s32[0]{0} constant({})
  %copy.8 = s32[0]{0} copy(%constant.7)
  ROOT %tuple.9 = (f32[4]{0}, s32[0]{0}) tuple(%subtract.6, %copy.8)
}
"""


@pytest.mark.parametrize("op_name, stage", [
    ("jit(_compress_jit)/fz.stage.resolve_eb/reduce_max", "resolve_eb"),
    ("jit(<lambda>)/jit(_compress_jit)/fz.stage.quantize/jit(lorenzo_quant)/"
     "fz.stage.collect_outliers/nonzero", "collect_outliers"),
    ("jit(_decompress_jit)/jit(bitunshuffle)/pallas_call", None),
    ("reduce_window_sum", None),
])
def test_stage_of(op_name, stage):
    assert stages.stage_of(op_name) == stage


def test_stage_map_hand_made():
    """Scoped instructions keep their innermost stage; what the compiler
    makes takes the stage inside what it calls, else of its users, else of
    its operands, else of the loop that runs it; the rest is ``other``.
    Constants, which the compiler shares across stages, name none."""
    m = stages.stage_map(HAND_MADE)
    assert m["negate.1 f32[4]"] == "dequantize"          # its own metadata
    assert m["multiply.3 f32[4]"] == "unshuffle"         # innermost of two
    assert m["fusion f32[4]"] == "dequantize"            # inside its computation
    assert m["copy.1 f32[4]"] == "dequantize"            # its user, the fusion
    assert m["reduce-window.2 f32[4]"] == "unshuffle"    # its user
    assert m["while.1 u32[]"] == "dequantize"            # through the loop's result
    assert m["add.1 f32[4]"] == "dequantize"             # the loop that runs it
    assert m["fusion.5 f32[4]"] == "dequantize"          # a shared constant names no stage
    assert m["copy.8 s32[0]"] == stages.OTHER            # nothing scoped near it
    assert m["a.1 f32[4]"] == "dequantize"


def test_split_and_unmapped_op():
    m = stages.stage_map(HAND_MADE)
    secs, missing = stages.split({"fusion f32[4]": 2.0, "multiply.3 f32[4]": 0.5,
                                  "copy.8 s32[0]": 0.25}, m)
    assert missing == []
    assert secs["dequantize"] == 2.0 and secs["unshuffle"] == 0.5
    assert secs[stages.OTHER] == 0.25 and sum(secs.values()) == 2.75
    secs, missing = stages.split({"fusion f32[4]": 2.0, "fusion.99 u16[8]": 1.0}, m)
    assert secs is None and missing == ["fusion.99 u16[8]"]
    # an instruction of the same name with another shape is another program's
    secs, missing = stages.split({"fusion f32[8]": 1.0}, m)
    assert secs is None


def test_workload_from_the_command_line():
    assert stages.workload(["--workload", "nyx-512.strict", "--seed", "3"]) == "nyx-512.strict"
    assert stages.workload(["-q", "tests/bench"]) is None


@dataclasses.dataclass
class _Ctx:
    trace: object


def test_reader_reports_nothing_for_an_unmapped_op(monkeypatch, capsys):
    m = {"compress": stages.stage_map(HAND_MADE), "decompress": stages.stage_map(HAND_MADE)}
    monkeypatch.setattr(stages, "workload", lambda: "a-cell")
    monkeypatch.setattr(stages, "maps", lambda root, name: m)
    trace = xplane.Reduced(window_s=1.0, span_s={"compress": 0.5, "decompress": 0.5},
                           calls={"compress": 2, "decompress": 2},
                           busy_s={"compress": 0.1, "decompress": 0.1}, busy_total_s=0.2,
                           op_s={"compress": {}, "decompress": {"fusion f32[4]": 0.004}},
                           op_n={"compress": {}, "decompress": {"fusion f32[4]": 2}},
                           gaps=[])
    assert stages.stage_ms(_Ctx(trace), "decompress", "dequantize") == pytest.approx(2.0)
    assert stages.stage_ms(_Ctx(trace), "decompress", "other") == 0.0
    assert stages.stage_ms(_Ctx(trace), "compress", "quantize") is None   # no ops
    trace.op_s["decompress"]["fusion.7 u16[8,64]"] = 0.001
    assert stages.stage_ms(_Ctx(trace), "decompress", "dequantize") is None
    assert "fusion.7 u16[8,64]" in capsys.readouterr().err
    monkeypatch.setattr(stages, "maps", lambda root, name: None)   # no scopes
    assert stages.stage_ms(_Ctx(trace), "decompress", "other") is None


KERNEL_STAGE = {"lorenzo_quant": "quantize", "bitshuffle_flag": "shuffle_encode",
                "bitunshuffle_tiles": "unshuffle"}


def _recorded():
    """A trace recorded on a v5e: 3 compress and 3 decompress calls of a
    strict-mode 4x64x512 field under ``bench.*`` spans, with the compiled
    text (``fz.lowered(...).compile().as_text()`` on the same chip, kernel
    bodies, backend configs and stack frames dropped) of the two programs
    those calls dispatched."""
    devices, host = xplane.read(str(DATA / "stages.xplane.pb"))
    texts = {d: (DATA / f"stages.{d}.hlo.txt").read_text() for d in xplane.DIRECTIONS}
    return xplane.reduce(devices, host), texts


@pytest.mark.parametrize("d", xplane.DIRECTIONS)
def test_join_recorded_trace(d):
    r, texts = _recorded()
    assert r.calls == {"compress": 3, "decompress": 3}
    smap = stages.stage_map(texts[d])
    secs, missing = stages.split(r.op_s[d], smap)
    assert missing == []
    total = sum(r.op_s[d].values())
    assert sum(secs.values()) == pytest.approx(total, rel=1e-12)
    assert all(secs[s] > 0 for s in stages.STAGES[d])      # strict mode: every stage
    assert secs[stages.OTHER] < 0.02 * total
    assert {s for s, v in secs.items() if v} <= set(stages.STAGES[d])
    kernels = {xplane.kernel_base(k): smap[k] for k in r.op_s[d]
               if xplane.kernel_base(k) in KERNEL_STAGE}
    assert kernels and all(KERNEL_STAGE[k] == s for k, s in kernels.items())


def test_recorded_trace_against_another_program():
    """Joined to the other direction's program, or with one op the program
    does not hold, the ops give no stage split."""
    r, texts = _recorded()
    secs, missing = stages.split(r.op_s["compress"], stages.stage_map(texts["decompress"]))
    assert secs is None and missing
    ops = dict(r.op_s["decompress"], **{"fusion.999 u16[8,64]": 1e-6})
    secs, missing = stages.split(ops, stages.stage_map(texts["decompress"]))
    assert secs is None and missing == ["fusion.999 u16[8,64]"]
