"""The host-device clock offset and the split of between-call gaps."""
import pathlib

import pytest

from bench import clock, xplane

DATA = pathlib.Path(__file__).parent / "data"
E = xplane.Event


RECORDED = ["tiny.xplane.pb", "stages.xplane.pb"]


@pytest.mark.parametrize("trace", RECORDED)
def test_offset_on_recorded_trace(trace):
    """On a v5e trace the runtime's launch and completion events bound the
    offset to a non-empty interval, and with it every program execution
    sits inside its host launch-to-completion window."""
    devices, host = xplane.read(str(DATA / trace))
    (modules, _), = devices.values()
    low, high = clock.offset(modules, host)
    assert 0 < low <= high < 5e-3
    launches = sorted(e.start for e in host if e.name == clock.LAUNCH)
    dones = sorted(e.start for e in host if e.name == clock.DONE)
    for m, la, dn in zip(sorted(modules, key=lambda m: m.start), launches, dones):
        assert la <= m.start + low and m.end + low <= dn


def _two_calls():
    """A compress then a decompress, device clock 2 behind the host's.
    Host: bench.compress [0, 10], its fz span [1, 4] with PjitFunction from
    1.5; bench.decompress [10.5, 20], fz [12, 14], PjitFunction from 12.25.
    Device: compress [0, 7] with ops [1, 3] and [4, 7]; decompress [12, 15]
    with op [12.5, 15]. Launches at 2.5 and 13, completions seen at 9.5 and
    19."""
    host = [E("bench.compress", 0, 10), E("fz.compress", 1, 4),
            E("PjitFunction(_compress_jit)", 1.5, 4), E(clock.LAUNCH, 2.5, 3),
            E(clock.DONE, 9.5, 9.6),
            E("bench.decompress", 10.5, 20), E("fz.decompress", 12, 14),
            E("PjitFunction(_decompress_jit)", 12.25, 14),
            E(clock.LAUNCH, 13, 13.2), E(clock.DONE, 19, 19.1)]
    modules = [E("jit__compress_jit(1)", 0, 7), E("jit__decompress_jit(2)", 12, 15)]
    ops = [E("%a = f32[4]{0} fusion(%x)", 1, 3), E("%b = f32[4]{0} fusion(%x)", 4, 7),
           E("%c = f32[4]{0} fusion(%y)", 12.5, 15)]
    return modules, ops, host


def test_gap_phases_hand_made():
    modules, ops, host = _two_calls()
    low, high = clock.offset(modules, host)
    assert (low, high) == (2.5, 2.5)    # max(2.5 - 0, 13 - 12), min(9.5 - 7, 19 - 15)
    out = clock.gap_phases(modules, ops, host, low)
    # the gap is device [7, 12.5], host [9.5, 15]: 5.5 s
    assert out["compress"] == {"completion": 0.5, "caller": 0, "wrapper": 0,
                               "dispatch": 0, "between": 0.5}
    assert out["decompress"] == {"completion": 0, "caller": 2.0, "wrapper": 0.25,
                                 "dispatch": 2.75, "between": 4.5}
    total = sum(v for d in out.values() for k, v in d.items() if k != "between")
    assert total == pytest.approx(12.5 - 7)


@pytest.mark.parametrize("trace", RECORDED)
def test_gap_phases_sum_to_every_gap_on_recorded_trace(trace):
    devices, host = xplane.read(str(DATA / trace))
    (modules, ops), = devices.values()
    low, _ = clock.offset(modules, host)
    out = clock.gap_phases(modules, ops, host, low)
    phases = sum(v for d in out.values() for k, v in d.items() if k != "between")
    holes = sum(s for name, s in xplane.reduce(devices, host, 100).gaps
                if name.startswith("host between calls"))
    assert phases == pytest.approx(holes, rel=1e-9)
    for d in xplane.DIRECTIONS:
        assert all(v >= 0 for v in out[d].values())
        assert out[d]["between"] <= sum(out[d][p] for p in clock.PHASES)


def test_unpaired_events_give_nothing():
    modules, ops, host = _two_calls()
    assert clock.offset(modules, host[:-1]) is None           # a completion lost
    crossed = [e if e.name != clock.DONE else E(e.name, e.start - 5, e.end)
               for e in host]
    assert clock.offset(modules, crossed) is None             # bounds cross
    assert clock.gap_phases(modules, ops, host[1:], 2.5) is None
    no_wrapper = [e for e in host if e.name != "fz.decompress"]
    assert clock.gap_phases(modules, ops, no_wrapper, 2.5) is None
