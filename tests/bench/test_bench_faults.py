"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference in bfloat16) fails the comparison.

Runs go through the harness on the CPU at a tiny size (the look for a chip
skipped), with the Pallas kernels interpreted.
"""
import pytest

from bench import control, faults, harness, reference


@pytest.mark.parametrize("name", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", ["nyx-512.strict", "hurricane-isabel.paper"])
def test_fault_makes_the_run_incorrect(tiny_root, no_cache, monkeypatch, cell, name):
    from repro.core import fz
    comp, dec = faults.FAULTS[name](fz.compress, fz.decompress)
    monkeypatch.setattr(fz, "compress", comp)
    monkeypatch.setattr(fz, "decompress", dec)
    out = harness.run(harness.load_cell(cell, tiny_root), 31, 0.3, False,
                      t_start=0.0, require_tpu=False, log=lambda msg: None)
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert any(v["value"] > v["limit"] for v in out["checks"].values())


@pytest.mark.parametrize("cell", ["nyx-512.paper", "hurricane-isabel.strict"])
def test_control_fails_where_the_program_passes(tiny_root, cell):
    seen = []
    summary = control.readings(harness.load_cell(cell, tiny_root),
                               [5, 2**32 + 5, 11], 1, seen.append)
    programs = [s for s in seen if s["kind"] == "program"]
    controls = [s for s in seen if s["kind"] == "control"]
    assert len(programs) == len(controls) == 3
    for s in programs:
        assert reference.verdict(s)
    for s in controls:
        assert not reference.verdict({**s, "truncated_mismatch": 0})
    for name in faults.FAULTS:
        assert not reference.verdict(summary[f"fault:{name}"])
