"""repro.obs unit tier: registry semantics, histogram quantile accuracy vs
numpy, span nesting/reentrancy (including the jit discipline: spans compile
to no-ops inside traced regions and ``span_traces`` counts compilations),
Chrome trace schema, sentinel triggering, and ring bounding."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import sentinels, spans


@pytest.fixture(autouse=True)
def clean_obs():
    """Each test sees a fresh registry + event ring (process-global state)."""
    obs.reset()
    obs.clear_events()
    yield
    obs.reset()
    obs.clear_events()


# -- registry ----------------------------------------------------------------

def test_counter_gauge_identity_and_labels():
    c1 = obs.counter("reqs", op="compress")
    c1.inc()
    c1.inc(4)
    # same (name, labels) -> same instance; different labels -> different
    assert obs.counter("reqs", op="compress") is c1
    assert obs.counter("reqs", op="decompress") is not c1
    assert c1.value == 5
    g = obs.gauge("depth")
    g.set(3)
    g.max(1)          # high-water keeps the larger
    assert g.value == 3.0
    g.max(9)
    assert g.value == 9.0
    snap = obs.snapshot()
    assert snap["counters"]["reqs{op=compress}"] == 5
    assert snap["counters"]["reqs{op=decompress}"] == 0
    assert snap["gauges"]["depth"] == 9.0
    json.dumps(snap)   # snapshot must be JSON-ready


def test_metric_kind_collision_raises():
    obs.counter("x")
    with pytest.raises(TypeError):
        obs.gauge("x")


def test_disabled_suspends_all_recording():
    with obs.disabled():
        obs.counter("c").inc()
        obs.gauge("g").set(5)
        obs.histogram("h").observe(1.0)
        with obs.span("quiet"):
            pass
    snap = obs.snapshot()
    assert snap["counters"]["c"] == 0
    assert snap["gauges"]["g"] == 0.0
    assert snap["histograms"]["h"]["count"] == 0
    assert spans.events() == []
    obs.counter("c").inc()     # re-enabled on exit
    assert obs.counter("c").value == 1


def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    for name, data in [
        ("lognorm", rng.lognormal(0.0, 2.0, 5000)),
        ("uniform", rng.uniform(0.5, 100.0, 5000)),
        ("heavy", rng.pareto(1.5, 5000) + 1.0),
    ]:
        h = obs.histogram(name)
        for v in data:
            h.observe(v)
        assert h.count == len(data)
        assert h.min == data.min() and h.max == data.max()
        assert h.sum == pytest.approx(data.sum())
        for q in (10, 50, 90, 99):
            exact = float(np.percentile(data, q))
            est = h.percentile(q)
            # log-bucketed at base 2**(1/8) -> ~9% relative resolution
            assert est == pytest.approx(exact, rel=0.12), (name, q)
        assert h.percentile(0) == data.min()
        assert h.percentile(100) == data.max()


def test_histogram_zero_and_negative_do_not_blow_up():
    h = obs.histogram("edge")
    h.observe(0.0)
    h.observe(-3.0)
    h.observe(2.0)
    assert h.count == 3
    assert h.percentile(100) == 2.0
    assert h.percentile(0) == -3.0


# -- spans -------------------------------------------------------------------

def test_span_nesting_depth_parent_and_timing():
    with obs.span("outer", job=1):
        assert spans.current_stack() == ("outer",)
        with obs.span("inner"):
            assert spans.current_stack() == ("outer", "inner")
    assert spans.current_stack() == ()
    evs = spans.events()
    # inner closes first
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert outer["depth"] == 0 and outer["parent"] is None
    assert outer["dur"] >= inner["dur"] > 0
    # temporal nesting: inner's window sits inside outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"]["job"] == 1
    assert obs.counter("span_calls", span="outer").value == 1
    h = obs.DEFAULT.find("span_ms", span="outer")
    assert h is not None and h.count == 1


def test_span_reentrant_and_exception_safe():
    s = obs.span("recurse")

    def go(n):
        with s:
            if n:
                go(n - 1)

    go(3)
    assert obs.counter("span_calls", span="recurse").value == 4
    assert spans.current_stack() == ()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    assert spans.current_stack() == ()       # stack restored on exception
    assert obs.counter("span_calls", span="boom").value == 1


def test_span_decorator():
    @obs.span("deco")
    def f(x):
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert obs.counter("span_calls", span="deco").value == 2


def test_span_attrs_never_retain_tracers():
    @jax.jit
    def f(x):
        with obs.span("traced", val=x):     # x is a tracer here
            return x * 2

    f(jnp.ones(4))
    (ev,) = [e for e in spans.events() if e["name"] == "traced"]
    assert isinstance(ev["args"]["val"], str)    # stringified, not retained


def test_span_jit_discipline_no_runtime_events_and_retrace_detector():
    @jax.jit
    def f(x):
        with obs.span("jit.body"):
            return x * 2 + 1

    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_allclose(f(x), 2 * x + 1)   # compile #1
    evs = [e for e in spans.events() if e["name"] == "jit.body"]
    assert len(evs) == 1 and evs[0]["cat"] == "jit-trace"
    assert obs.counter("span_traces", span="jit.body").value == 1
    assert obs.counter("span_calls", span="jit.body").value == 0

    # executing the compiled program records nothing: span is a no-op at
    # runtime, so repeated calls add no events and bump no counters
    for _ in range(5):
        f(x)
    assert len([e for e in spans.events() if e["name"] == "jit.body"]) == 1
    assert obs.counter("span_traces", span="jit.body").value == 1

    # a new shape retraces: span_traces is the retrace detector
    f(jnp.arange(16, dtype=jnp.float32))
    assert obs.counter("span_traces", span="jit.body").value == 2


def test_span_eager_wrapper_contains_trace_time_events():
    """The acceptance-criteria nesting: an eager wrapper span triggering a
    compilation temporally contains the jit-trace event of its inner span."""
    @jax.jit
    def inner(x):
        with obs.span("stage"):
            return x + 1

    with obs.span("wrapper"):
        inner(jnp.ones(4))
    evs = {e["name"]: e for e in spans.events()}
    w, s = evs["wrapper"], evs["stage"]
    assert w["cat"] == "span" and s["cat"] == "jit-trace"
    assert w["ts"] <= s["ts"]
    assert s["ts"] + s["dur"] <= w["ts"] + w["dur"] + 1e-6


def test_ring_bounded_under_flood():
    spans.set_ring_capacity(512)
    try:
        n = 1_000_000
        for i in range(n):
            spans._record(f"e{i}", "span", float(i), 1.0, 0, None, {})
        evs = spans.events()
        assert len(evs) == 512 == spans.ring_capacity()
        # ring keeps the newest events
        assert evs[0]["name"] == f"e{n - 512}"
        assert evs[-1]["name"] == f"e{n - 1}"
    finally:
        spans.set_ring_capacity(spans.DEFAULT_RING_CAPACITY)


# -- chrome trace ------------------------------------------------------------

def test_chrome_trace_schema(tmp_path):
    with obs.span("a"):
        with obs.span("b"):
            pass
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(str(path), metadata={"run": "unit"})
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"] == {"run": "unit"}
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"a", "b"}
    for e in xs:
        for k in ("name", "cat", "ph", "ts", "dur", "pid", "tid", "args"):
            assert k in e
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0
    assert ms and all(e["name"] == "thread_name" for e in ms)
    # every X event's (pid, tid) has a thread_name metadata row
    assert {(e["pid"], e["tid"]) for e in xs} <= {(e["pid"], e["tid"])
                                                  for e in ms}


# -- sentinels ---------------------------------------------------------------

def test_sentinel_eb_sampling_first_then_every_nth():
    old = sentinels.CONFIG
    sentinels.configure(sentinels.SentinelConfig(eb_sample_every=4))
    try:
        picks = [sentinels.should_check_eb("t") for _ in range(9)]
        assert picks == [True, False, False, False,
                         True, False, False, False, True]
    finally:
        sentinels.configure(old)


def test_sentinel_eb_violation_trips_assert_healthy():
    assert sentinels.check_error_bound("kv_cold", max_err=1e-4, eb_abs=1e-3)
    sentinels.assert_healthy()               # in-bound check: healthy
    assert not sentinels.check_error_bound("kv_cold", max_err=5e-3,
                                           eb_abs=1e-3)
    assert obs.violations() == {"sentinel_eb_violations{tier=kv_cold}": 1}
    with pytest.raises(sentinels.HealthError):
        sentinels.assert_healthy()


def test_sentinel_eb_f32_rounding_allowance():
    # max_err just over eb but within the |x|*2^-22 rounding allowance
    eb = 1e-3
    max_abs = 1e4
    allowance = max_abs * 2.0 ** -22
    assert sentinels.check_error_bound("t", eb * 1.0005 + allowance * 0.5,
                                       eb, max_abs)
    assert not sentinels.check_error_bound("t", eb + allowance * 3, eb,
                                           max_abs)


def test_sentinel_ratio_drift_flags_after_warmup_only():
    for _ in range(5):
        sentinels.note_ratio("wire", 4.0)
    assert obs.violations() == {}
    sentinels.note_ratio("wire", 100.0)      # >4x the EWMA -> drift
    assert obs.violations() == {"sentinel_ratio_drift{tier=wire}": 1}
    sentinels.assert_healthy()               # drift alone is not fatal...
    with pytest.raises(sentinels.HealthError):
        sentinels.assert_healthy(strict_drift=True)   # ...unless strict


def test_sentinel_scheduler_gauges():
    sentinels.note_scheduler(waiting=3, running=2, parked=1,
                             oldest_wait_steps=7)
    sentinels.note_scheduler(waiting=0, running=2, parked=0,
                             oldest_wait_steps=2)
    snap = obs.snapshot()["gauges"]
    assert snap["sched_waiting{subsystem=kvpool}"] == 0
    assert snap["sched_oldest_wait_steps{subsystem=kvpool}"] == 2
    assert snap["sched_max_wait_steps{subsystem=kvpool}"] == 7  # high-water


# -- step report -------------------------------------------------------------

def test_step_report_joins_spans_with_bytes():
    with obs.span("dist.bucket0_reduce"):
        pass
    rep = obs.step_report(bytes_by_tag={"bucket0_reduce": 1 << 20},
                          meta={"step": 3})
    (row,) = [r for r in rep.rows if r["span"] == "dist.bucket0_reduce"]
    assert row["calls"] == 1
    assert row["bytes"] == 1 << 20
    assert row["gbps"] > 0
    assert "dist.bucket0_reduce" in rep.render()
