"""repro.analysis: seeded-bad fixtures must flag; the real tree must be
clean modulo the committed baseline."""
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import carry, jitlint, report, resources
from repro.analysis.kernelspec import (BlockDecl, KernelSpec, ScratchDecl,
                                       probe_index_map, spec_builders)
from repro.core import quant
from repro.kernels import lorenzo_quant as lq
from repro.kernels import ref

RNG = np.random.default_rng(7)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# Resource pass: footprint model units + seeded over-budget specs
# ---------------------------------------------------------------------------

def test_padded_bytes_tile_model():
    # f32: (8, 128) is one native tile
    assert resources.padded_bytes((8, 128), 4) == 8 * 128 * 4
    # a scalar-ish block still occupies a full tile
    assert resources.padded_bytes((1, 1), 4) == 8 * 128 * 4
    # rank-1 lives on the lane axis: (300,) -> (8, 384)
    assert resources.padded_bytes((300,), 4) == 8 * 384 * 4
    # u16 sublane count is 16: (1, 128) pads the sublane axis 1 -> 16
    assert resources.padded_bytes((1, 128), 2) == 16 * 128 * 2
    # SMEM is raw bytes, no tile padding
    assert resources.padded_bytes((4,), 4, memory="smem") == 16


def test_seeded_vmem_overflow_flagged():
    spec = KernelSpec(
        name="bad_vmem", module="tests", grid=(4,),
        in_blocks=(BlockDecl("big", (4096, 4096), "float32",
                             index_map=lambda i: (i, 0)),),
        out_blocks=(BlockDecl("o", (8, 128), "float32",
                              index_map=lambda i: (i, 0)),),
        point="fixture")
    findings = resources.analyze_spec(spec)
    assert "vmem-overflow" in _rules(findings)
    # double-buffered 64MiB block dominates
    assert any("big" in f.message for f in findings)


def test_seeded_smem_overflow_flagged():
    spec = KernelSpec(
        name="bad_smem", module="tests", grid=(2,),
        in_blocks=(BlockDecl("x", (8, 128), "float32",
                             index_map=lambda i: (i, 0)),),
        out_blocks=(BlockDecl("o", (8, 128), "float32",
                              index_map=lambda i: (i, 0)),),
        scratch=(ScratchDecl("s", (100_000,), "int32", "smem"),),
        point="fixture")
    assert "smem-overflow" in _rules(resources.analyze_spec(spec))


def test_seeded_lane_underfill_and_pad_waste_flagged():
    spec = KernelSpec(
        name="bad_lanes", module="tests", grid=(2,),
        in_blocks=(
            # 1MiB buffer with an 8-wide trailing axis: 16x lane padding
            BlockDecl("narrow", (65536, 8), "uint16",
                      index_map=lambda i: (0, 0)),
            # trailing axis full, but sublane padding 1 -> 8 inflates 8x
            BlockDecl("thin", (130, 1, 128), "float32",
                      index_map=lambda i: (i, 0, 0)),
        ),
        out_blocks=(BlockDecl("o", (8, 128), "float32",
                              index_map=lambda i: (i, 0)),),
        critical_lanes=(("kv_tile", 8),),
        point="fixture")
    findings = resources.analyze_spec(spec)
    objs = {f.obj for f in findings if f.rule == "lane-underfill"}
    assert "bad_lanes.narrow" in objs
    assert "bad_lanes.kv_tile" in objs          # declared critical dim < 128
    assert any(f.rule == "pad-waste" and f.obj == "bad_lanes.thin"
               for f in findings)


def test_within_budget_spec_is_clean():
    spec = KernelSpec(
        name="ok", module="tests", grid=(8,),
        in_blocks=(BlockDecl("x", (8, 128), "float32",
                             index_map=lambda i: (i, 0)),),
        out_blocks=(BlockDecl("o", (8, 128), "float32",
                              index_map=lambda i: (i, 0)),),
        dimension_semantics=("parallel",), point="fixture")
    assert resources.analyze_spec(spec) == []


def test_band_helpers_cross_check_clean():
    assert resources.check_band_helpers() == []


def test_band_for_is_dtype_aware():
    # at the budget frontier, halving itemsize doubles the band
    t = 1 << 20
    assert lq.band_for(t, itemsize=4) == 1
    assert lq.band_for(t, itemsize=2) == 2
    # small trailing dims clamp at MAX_BAND for every itemsize
    assert lq.band_for(64, itemsize=4) == lq.MAX_BAND
    assert lq.band_for(64, itemsize=2) == lq.MAX_BAND


# ---------------------------------------------------------------------------
# Carry pass: seeded carry-under-parallel + correctly-declared variants
# ---------------------------------------------------------------------------

def _carry_kernel(x_ref, o_ref, acc_ref):
    acc = acc_ref[...]                    # read before any write: a carry
    acc_ref[...] = acc + x_ref[...]
    o_ref[...] = acc_ref[...]


def _per_step_kernel(x_ref, o_ref, tmp_ref):
    tmp_ref[...] = x_ref[...] * 2         # unguarded write first: per-step
    o_ref[...] = tmp_ref[...]


def _guarded_carry_kernel(x_ref, o_ref, acc_ref):
    import jax.experimental.pallas as pl  # noqa: F401  (body is AST-only)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)   # step-0 init, not a write

    acc_ref[...] += x_ref[...]            # read-modify-write: still a carry
    o_ref[...] = acc_ref[...]


def _spec_with(kernel_fn, semantics):
    return KernelSpec(
        name="fixture_kernel", module="tests", grid=(4,),
        in_blocks=(BlockDecl("x", (8, 128), "float32",
                             index_map=lambda i: (i, 0)),),
        out_blocks=(BlockDecl("o", (8, 128), "float32",
                              index_map=lambda i: (i, 0)),),
        scratch=(ScratchDecl("acc", (8, 128), "float32", "vmem"),),
        dimension_semantics=semantics, kernel_fn=kernel_fn, point="fixture")


def test_seeded_carry_under_parallel_flagged():
    findings = carry.analyze_spec(_spec_with(_carry_kernel, ("parallel",)))
    assert _rules(findings) == ["carry-under-parallel"]


def test_seeded_carry_without_semantics_flagged():
    findings = carry.analyze_spec(_spec_with(_carry_kernel, None))
    assert _rules(findings) == ["carry-default-semantics"]


def test_guarded_init_still_counts_as_carry():
    findings = carry.analyze_spec(
        _spec_with(_guarded_carry_kernel, ("parallel",)))
    assert "carry-under-parallel" in _rules(findings)


def test_carry_under_arbitrary_is_clean():
    assert carry.analyze_spec(_spec_with(_carry_kernel, ("arbitrary",))) == []


def test_per_step_scratch_allows_parallel():
    assert carry.analyze_spec(
        _spec_with(_per_step_kernel, ("parallel",))) == []


def test_per_step_scratch_missing_semantics_warns():
    findings = carry.analyze_spec(_spec_with(_per_step_kernel, None))
    assert _rules(findings) == ["missing-semantics"]
    assert all(f.severity == "warn" for f in findings)


def test_revisited_output_pins_only_ignored_axes():
    # flash-decode shape: out index map ignores the sequential axis 1
    def kernel(x_ref, o_ref):
        o_ref[...] += x_ref[...]

    spec = KernelSpec(
        name="revisit", module="tests", grid=(2, 4),
        in_blocks=(BlockDecl("x", (8, 128), "float32",
                             index_map=lambda b, t: (b, t)),),
        out_blocks=(BlockDecl("o", (8, 128), "float32",
                              index_map=lambda b, t: (b, 0)),),
        dimension_semantics=("parallel", "parallel"),
        kernel_fn=kernel, point="fixture")
    findings = carry.analyze_spec(spec)
    assert _rules(findings) == ["carry-under-parallel"]
    assert all("axis 1" in f.message for f in findings)
    spec_ok = KernelSpec(**{**spec.__dict__,
                            "dimension_semantics": ("parallel", "arbitrary")})
    assert carry.analyze_spec(spec_ok) == []


def test_star_refs_unpack_is_classified():
    def kernel(*refs):
        (x_ref, o_ref, acc_ref) = refs
        acc = acc_ref[...]
        acc_ref[...] = acc + x_ref[...]
        o_ref[...] = acc_ref[...]

    findings = carry.analyze_spec(_spec_with(kernel, ("parallel",)))
    assert _rules(findings) == ["carry-under-parallel"]


# ---------------------------------------------------------------------------
# jit-discipline linter: seeded bad sources through lint_source
# ---------------------------------------------------------------------------

def _lint(src, **kw):
    return jitlint.lint_source(textwrap.dedent(src), "fixture.py", **kw)


def test_seeded_traced_branch_flagged():
    findings = _lint("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    assert _rules(findings) == ["traced-branch"]


def test_traced_branch_in_kernel_body_flagged():
    findings = _lint("""
        def kernel(x_ref, o_ref):
            while x_ref[0] > 0:
                o_ref[...] = 1
    """)
    assert _rules(findings) == ["traced-branch"]


def test_static_branches_are_exempt():
    findings = _lint("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("mode",))
        def f(x, plan, mode="a"):
            if mode == "b":                  # static_argnames param
                return x
            if x is None:                    # None-ness is trace-static
                return plan
            if x.shape[0] > 2:               # array metadata
                return x
            if plan.kern_nd == 1:            # config-dataclass attribute
                return x
            return x
    """)
    assert findings == []


def test_seeded_host_calls_flagged():
    findings = _lint("""
        import numpy as np
        import jax

        @jax.jit
        def f(x):
            y = np.sum(x)
            z = float(x)
            w = x.item()
            return y + z + w
    """)
    assert _rules(findings) == ["host-call"]
    assert len(findings) == 3


def test_seeded_eager_obs_in_trace_flagged():
    findings = _lint("""
        import jax
        from repro import obs

        @jax.jit
        def f(x):
            obs.counter("fz.dispatch")
            with obs.span("fz.encode"):      # span is trace-safe: allowed
                return x
    """)
    assert _rules(findings) == ["eager-obs-in-trace"]


def test_seeded_unknown_static_arg_flagged():
    findings = _lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("shap",))
        def f(x, shape):
            return x
    """)
    assert _rules(findings) == ["unknown-static-arg"]


def test_seeded_unhashable_static_arg_flagged():
    findings = _lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("dims",))
        def f(x, dims=[1, 2]):
            return x
    """)
    assert _rules(findings) == ["unhashable-static-arg"]


def test_unjitted_python_is_not_linted():
    findings = _lint("""
        import numpy as np

        def f(x):
            if x > 0:
                return np.sum(x)
            return float(x)
    """)
    assert findings == []


def test_style_unused_import_and_noqa():
    findings = _lint("""
        from __future__ import annotations
        import os
        import sys  # noqa: F401
        import json

        def f():
            return json.dumps({})
    """, style=True)
    assert _rules(findings) == ["unused-import"]
    assert len(findings) == 1 and ":os" in findings[0].obj


# ---------------------------------------------------------------------------
# Real tree: clean modulo the committed baseline; specs cover every site
# ---------------------------------------------------------------------------

def test_real_tree_clean_modulo_baseline():
    rep = report.run_all()
    assert rep.clean, "new findings:\n" + rep.render_text()
    assert rep.stale == [], f"stale baseline entries: {rep.stale}"


def test_every_kernel_site_registers_a_spec():
    import repro.kernels  # noqa: F401  (importing populates the registry)
    assert set(spec_builders()) >= {
        "lorenzo_quant", "bitshuffle_flag.shuffle", "bitshuffle_flag.unshuffle",
        "flash_decode", "fused_compress", "fused_shuffle_encode",
        "fused_decode"}


def test_probe_index_map_classifies_axes():
    ignored, varies = probe_index_map(lambda b, t: (b, 0), (2, 4))
    assert ignored == (1,) and varies
    ignored, varies = probe_index_map(lambda i: (0, 0), (4,))
    assert ignored == (0,) and not varies


# ---------------------------------------------------------------------------
# Satellite: bf16 inputs stay native through the standalone quantizer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4096,), (33, 100)])
def test_lorenzo_quant_bf16_matches_f32_reference(shape):
    x = jnp.asarray(RNG.standard_normal(shape), jnp.bfloat16)
    eb = quant.snap_eb(jnp.float32(1e-2))
    k = lq.lorenzo_quant(x, eb, interpret=True)
    r = ref.lorenzo_quant_ref(x, eb)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
