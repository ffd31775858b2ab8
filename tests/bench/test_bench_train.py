"""The training kind of cell: its files, the metric readers, the trace and
wire reductions, the reference, and whole runs through ``harness.run``.

The whole runs need a four-device mesh, so they go to a child process
that asks the CPU backend for four devices; the configuration is cut to
the program's smoke widths (``conftest.make_tiny_root``). One child makes
every run and prints one JSON line for each; the tests read those lines.
"""
import json
import math
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from bench import flops, harness, steptrace, train, train_faults, wire
from bench import train_reference as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "yi-6b-dp4.fz"
SEED = 2**33 + 5


def test_train_cell_is_found():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.config["kind"] == "train"
    assert set(cell.end_to_end) == {"train_tokens_per_s", "mfu", "workspace_gib", "setup_s"}
    assert set(cell.per_layer) == {"idle_share.step", "step_ms.fz_compress",
                                   "step_ms.fz_decompress", "step_ms.gather", "step_ms.other"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.reader(ROOT, m))


def test_configuration_holds_the_published_widths():
    """Every width of the file is Yi-6B's as the program's own catalog
    holds it; only the depth is cut, as ``reduced`` says."""
    from repro.configs import yi_6b
    conf = harness.load_cell(CELL).config
    pub = yi_6b.CONFIG
    assert (conf["hidden_size"], conf["intermediate_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["vocab_size"], conf["rope_theta"]) == \
        (pub.d_model, pub.d_ff, pub.n_heads, pub.n_kv_heads, pub.vocab, pub.rope_theta)
    assert conf["hidden_size"] // conf["num_attention_heads"] == pub.hd
    assert conf["reduced"] == {"num_hidden_layers": [pub.n_layers, conf["num_hidden_layers"]]}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == conf["name"])
    assert entry["reduced"] == list(conf["reduced"])


def test_flops_per_token_by_the_palm_count():
    conf = harness.load_cell(CELL).config
    d, ff, v, kvd = 4096, 11008, 64000, 4 * 128
    matmul = 2 * d * d + 2 * d * kvd + 3 * d * ff + d * v
    assert flops.per_token(conf, 512) == 6 * matmul + 12 * 512 * d
    assert flops.per_token(conf, 512) == 2636120064


def _context(trace=None, classes=None, peak=4 * 197e12):
    window = train.Window(steps=5, tokens=5 * 4096, seconds=30.0, step_s=[6.0] * 5)
    return train.Context(setup_s=95.5, window=window, workspace_bytes=3 * 2**30,
                         flops_per_token=2636120064.0, peak_flops_per_s=peak,
                         trace=trace, classes=classes)


def _trace():
    op_s = {"fusion.1 f32[8]": 1.0, "all-gather-start.2 u16[2,8]": 0.5,
            "fusion.3 u16[8]": 2.0, "convolution.4 bf16[8]": 2.5}
    return steptrace.StepTrace(steps=2, window_s=12.0, span_s=12.0,
                               busy_s={"/device:TPU:0": 6.0, "/device:TPU:1": 9.0},
                               op_s=op_s, op_n=dict.fromkeys(op_s, 1), gaps=[])


CLASSES = {"fusion.1 f32[8]": "fz_decompress", "all-gather-start.2 u16[2,8]": "gather",
           "fusion.3 u16[8]": "fz_compress", "convolution.4 bf16[8]": "other"}


def test_readers_on_a_fixed_context():
    ctx = _context(_trace(), CLASSES)
    read = {m: harness.reader(ROOT, m)(ctx) for m in
            ("train_tokens_per_s", "mfu", "workspace_gib", "setup_s", "idle_share.step",
             "step_ms.fz_compress", "step_ms.fz_decompress", "step_ms.gather",
             "step_ms.other")}
    assert read["train_tokens_per_s"] == 5 * 4096 / 30.0
    assert math.isclose(read["mfu"], 100 * (5 * 4096 / 30.0) * 2636120064 / (4 * 197e12))
    assert read["workspace_gib"] == 3.0 and read["setup_s"] == 95.5
    assert math.isclose(read["idle_share.step"], 100 * (1 - 7.5 / 12.0))
    assert (read["step_ms.fz_compress"], read["step_ms.fz_decompress"],
            read["step_ms.gather"], read["step_ms.other"]) == (1000.0, 500.0, 250.0, 1250.0)
    # the four classes sum to the first chip's device op time a step
    assert sum(read[f"step_ms.{c}"] for c in steptrace.CLASSES) == 1e3 * 6.0 / 2


def test_readers_find_nothing_to_read():
    """Untraced, off a chip, or when the trace holds ops the step lacks,
    the step readers return nothing rather than a made-up number."""
    assert _context(peak=None).mfu() is None
    ctx = _context()
    for m in ("idle_share.step", "step_ms.other", "step_ms.gather"):
        assert harness.reader(ROOT, m)(ctx) is None
    foreign = dict(CLASSES)
    del foreign["fusion.3 u16[8]"]
    assert _context(_trace(), foreign).step_ms("other") is None


STEP_TEXT = """\
HloModule jit_step

%region.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(f32[8]{0} %a), metadata={op_name="jit(step)/fz.decompress/fz.stage.dequantize/neg"}
}

ENTRY %main (p: f32[8], g: u16[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %g = u16[8]{0} parameter(1)
  %dot.1 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(step)/transpose(jvp(pod_loss))/mul"}
  %copy.1 = f32[8]{0} copy(f32[8]{0} %dot.1)
  %fusion.2 = u16[8]{0} fusion(f32[8]{0} %copy.1), kind=kLoop, calls=%region.1, metadata={op_name="jit(step)/fz.compress/fz.stage.compact_blocks/gather"}
  %all-gather-start.1 = (u16[8]{0}, u16[16]{0}) all-gather-start(u16[8]{0} %fusion.2), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(step)/shard_map/all_gather"}
  %all-gather-done.1 = u16[16]{0} all-gather-done((u16[8]{0}, u16[16]{0}) %all-gather-start.1)
  %all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p), channel_id=2, replica_groups=[2,2]<=[4], to_apply=%region.1, metadata={op_name="jit(step)/psum"}
  %fusion.3 = f32[8]{0} fusion(u16[16]{0} %all-gather-done.1), kind=kLoop, calls=%region.1
  %reduce-window.4 = f32[8]{0} reduce-window(f32[8]{0} %fusion.3), window={size=8 pad=7_0}, to_apply=%region.1, metadata={op_name="jit(step)/shard_map/while/body/jit(_decompress_jit)/reduce-window.8"}
  ROOT %while.5 = f32[8]{0} while(f32[8]{0} %reduce-window.4), condition=%region.1, body=%region.1, metadata={op_name="jit(step)/shard_map/while"}
}
"""


def test_step_ops_take_their_class():
    got = steptrace.classes(STEP_TEXT, per_pod=2)
    assert got["dot.1 f32[8]"] == "other"
    assert got["copy.1 f32[8]"] == "fz_compress"          # no metadata: its user's
    assert got["fusion.2 u16[8]"] == "fz_compress"
    assert got["all-gather-start.1 u16[8]"] == "gather"    # groups {0,2},{1,3}
    assert got["all-gather-done.1 u16[16]"] == "gather"
    assert got["all-reduce.1 f32[8]"] == "other"           # in-pod groups {0,1},{2,3}
    assert got["fusion.3 f32[8]"] == "fz_decompress"       # what it calls
    # rebuilt by the compiler inside the decompress program, stage lost
    assert got["reduce-window.4 f32[8]"] == "fz_decompress"
    assert got["while.5 f32[8]"] == steptrace.CONTAINER


def test_a_loops_own_event_is_not_counted():
    """A loop's device event spans the events of the ops it runs."""
    trace = steptrace.StepTrace(steps=1, window_s=1.0, span_s=1.0, busy_s={},
                                op_s={"while.5 f32[8]": 0.9, "fusion.3 f32[8]": 0.8,
                                      "dot.1 f32[8]": 0.1}, op_n={}, gaps=[])
    classes = steptrace.classes(STEP_TEXT, per_pod=2)
    secs, missing = steptrace.split(trace, classes)
    assert secs == {"fz_compress": 0.0, "fz_decompress": 0.8, "gather": 0.0, "other": 0.1}
    assert missing == 0.0
    assert [k for k, _ in train.breakdown(trace, classes)["device_ops"]] == \
        ["fz_decompress:fusion.3 f32[8]", "other:dot.1 f32[8]"]


def test_replica_groups_across_pods():
    assert wire.crosses_pod("replica_groups=[2,2]<=[2,2]T(1,0)", 2)
    assert not wire.crosses_pod("replica_groups=[2,2]<=[4]", 2)
    assert wire.crosses_pod("replica_groups={{0,2},{1,3}}", 2)
    assert not wire.crosses_pod("replica_groups={{0,1},{2,3}}", 2)
    assert not wire.crosses_pod("no groups", 2)


def test_wire_check_counts_what_crosses():
    moved, outs = wire.cross_pod(STEP_TEXT, per_pod=2)
    assert moved == {"all-gather": 32.0} and outs == {("all-gather", "u16", 16)}
    want = {"gathers": {("u16", 16)}, "large": {("u16", 16)}, "gather_bytes": (32, 32),
            "all_reduce_bytes": 8}
    assert wire.check(STEP_TEXT, 2, want) == dict.fromkeys(wire.LIMITS, 0)
    # a full-precision gradient all-reduced across pods
    leaked = STEP_TEXT.replace("replica_groups=[2,2]<=[4]", "replica_groups={{0,2},{1,3}}")
    got = wire.check(leaked, 2, want)
    assert got["wire_all_reduce_excess"] == 2 * 32 - 8
    # the containers left out of the exchange
    got = wire.check(STEP_TEXT.replace("T(1,0)", ""), 2, want)
    assert got["wire_gather_bytes_off"] == 32 and got["wire_shapes_off"] == 1


def test_reference_rows_are_seeded():
    a = reference.batch(512, 64, 8, 2**40 + 3, 2)
    assert a.shape == (8, 65) and a.dtype == np.int32
    assert np.array_equal(a, reference.batch(512, 64, 8, 2**40 + 3, 2))
    assert not np.array_equal(a, reference.batch(512, 64, 8, 2**40 + 3, 3))
    assert len({r.tobytes() for r in a}) == 8 and 0 <= a.min() and a.max() < 512
    # a repeat is its predecessor + 1; about half the tokens repeat
    rep = np.mean(a[:, 1:] == (a[:, :-1] + 1) % 512)
    assert 0.4 < rep < 0.7


def test_reference_schedule_is_the_programs():
    """Checked against the program here; the reference imports none of it."""
    import jax.numpy as jnp
    from repro.optim import warmup_cosine
    for step in range(6):
        want = float(warmup_cosine(jnp.int32(step), peak_lr=3e-4, warmup_steps=1,
                                   total_steps=3))
        assert math.isclose(reference.schedule(step, 3e-4, 1, 3), want, rel_tol=1e-6)


def test_compare_takes_the_worst_leaf():
    ref = {"losses": [10.0, 10.0, 12.0], "grad_norms": [1.0, 2.0, 4.0, 1e-6],
           "change_norms": [1.0, 1.0, 2.0, 5.0]}
    prog = {"losses": [10.0, 10.1, 12.0], "grad_norms": [1.1, 2.0, 4.0, 0.3],
            "change_norms": [1.0, 1.5, 2.0, 0.0]}
    got = train.compare(prog, ref)
    assert math.isclose(got["loss_rel_diff"], 0.01)
    # against the median leaf's norm where the leaf's own is smaller
    assert math.isclose(got["grad_norm_gap"], (0.3 - 1e-6) / 1.5)
    # the last leaf's reference gradient is nought: left out of the change
    assert math.isclose(got["change_norm_gap"], 0.5)


CHILD = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], sys.argv[2]]
root = __import__("pathlib").Path(sys.argv[3])
import jax
from bench import harness, train_control, train_faults
from repro.launch import compile_cache
compile_cache.use_compile_cache = lambda: None
cell = harness.load_cell("yi-6b-dp4.fz", root)


def run(name, trace=False):
    out = harness.run(cell, int(sys.argv[4]), 0.0, trace, t_start=time.perf_counter(),
                      require_tpu=False, log=lambda msg: None)
    print(json.dumps({"run": name, **out}), flush=True)


run("sound")
run("traced", trace=True)
for name, fault in train_faults.FAULTS.items():
    undo = []

    def setattr_(obj, attr, value):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)
    fault(setattr_)
    try:
        run(name)
    finally:
        for obj, attr, value in reversed(undo):
            setattr(obj, attr, value)
seen = []
summary = train_control.readings(cell, [7, 2**32 + 11], 2, jax.devices(), seen.append)
print(json.dumps({"run": "readings", "seen": seen, "summary": summary}), flush=True)
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory, tiny_root_maker):
    root = tiny_root_maker(tmp_path_factory.mktemp("train"))
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT / "src"), str(ROOT),
                        str(root), str(SEED)], env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, f"{p.stdout[-3000:]}\n{p.stderr[-5000:]}"
    lines = [json.loads(s) for s in p.stdout.splitlines() if s.startswith("{")]
    return {r["run"]: r for r in lines}


def test_train_run_on_four_cpu_devices(runs):
    out = runs["sound"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"train_tokens_per_s", "workspace_gib", "setup_s"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 4
    assert all(v["value"] <= v["limit"] for v in out["checks"].values())
    assert set(out["checks"]) == {"loss_rel_diff", "grad_norm_gap", "change_norm_gap",
                                  "nonfinite_losses", *wire.LIMITS}


def test_traced_train_run(runs):
    out = runs["traced"]
    assert out["correct"] is True and out["attempted"] >= 2
    assert {"busy_s", "window_s"} <= set(out["device"]) and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name", sorted(train_faults.FAULTS))
def test_train_fault_makes_the_run_incorrect(runs, name):
    out = runs[name]
    assert out["correct"] is False
    assert any(v["value"] > v["limit"] for v in out["checks"].values())


def test_control_and_reference_faults_part_from_the_program(runs):
    """The program's readings pass the cell's limits; the control's and
    each reference fault's read, on some number, at least three times the
    program's largest."""
    seen, summary = runs["readings"]["seen"], runs["readings"]["summary"]
    limits = harness.load_cell(CELL).traffic["limits"]
    programs = [s for s in seen if s["kind"] == "program"]
    assert len(programs) == 2
    for s in programs:
        assert all(s[k] <= lim for k, lim in limits.items())
    for kind in ["control"] + [f"fault:{f}" for f in reference.FAULTS]:
        rows = [s for s in seen if s["kind"] == kind]
        assert len(rows) == 2
        assert all(any(s[k] >= 3 * summary["program"][k] for k in limits) for s in rows), kind
        assert set(summary[kind]) == set(limits)


def test_field_run_keeps_its_result_form(tiny_root, no_cache):
    """A field cell runs through the same entry as before the kinds, with
    the same result keys and the same checks."""
    out = harness.run(harness.load_cell("hurricane-isabel.paper", tiny_root), SEED, 0.3,
                      False, t_start=0.0, require_tpu=False, log=lambda msg: None)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert out["correct"] is True
    assert {k: v["limit"] for k, v in out["checks"].items()} == \
        {"mismatch": 0, "max_err_over_eb": 1.0, "truncated_mismatch": 0}
    assert out["checks"]["mismatch"]["value"] == 0
    assert out["checks"]["truncated_mismatch"]["value"] == 0
    assert 0 < out["checks"]["max_err_over_eb"]["value"] <= 1.0
