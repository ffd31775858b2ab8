"""Paper Figure 10: per-kernel effect of the proposed optimizations.

Wall-clock per stage pair (relative speedups are the claim; absolute GB/s
needs the target TPU — on CPU the Pallas kernels run under the interpreter,
on TPU the same calls lower to Mosaic because every kernel variant routes
through the shared backend check ``repro.kernels.ops.backend_interpret()``
instead of hardcoding interpret mode). Version pairs mirror the paper's bars:

  pred-quant-v1     dual-quantization with the cuSZ-style outlier side path
  pred-quant-v2     optimized: branch-free saturating codes (paper §3.2)
  shuffle-mark-v1   bitshuffle and zero-flagging as two passes
  shuffle-mark-v2   fused single pass (paper §3.4 fusion)
  encode-v1/v2      phase-2 encode fed by v1 vs v2 quantization (the v2
                    codes produce fewer non-zero blocks -> faster compaction)

Beyond the paper's bars, the staged-vs-fused section times the three whole
execution paths (reference / staged kernels / single-launch megakernels) in
both directions, with two traffic columns:

  * ``hbm_model_bytes`` — analytic per-variant HBM traffic: input + outputs
    plus 4 bytes/elem for every u16 stream a staged pipeline round-trips
    (write + read of codes, then of shuffled words) and 8 bytes/elem for the
    reference path's int32 pre-quant stream. The fused megakernels' model is
    exactly input + outputs: their streams live in VMEM.
  * ``measured_traffic`` — ``hlo_cost.compiled_memory_traffic`` ratio of the
    actually-compiled program ((args + outs + 2*temps) / (args + outs)).
    Honest on TPU; under the CPU interpreter the megakernels' loop carries
    inflate their compress-side temps (see the helper's docstring), so the
    analytic column is the claim and this one is the measurement floor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import encode as enc
from repro.core import fz, quant, shuffle
from repro.data import make_field
from repro.kernels import ops as kops
from repro.launch import hlo_cost
from .common import FZ_PATHS, fz_path_config, gbps, timeit


def _pad_flat(codes):
    return shuffle.pad_to_tiles(codes.reshape(-1))


def hbm_model_bytes(path: str, direction: str, n: int, out_bytes: int) -> int:
    """Analytic HBM bytes for one (path, direction) variant on an n-element
    f32 field. Streams are u16 (2 bytes/elem); a round trip costs a write
    plus a read (4 bytes/elem)."""
    io = 4 * n + out_bytes                   # float field + container, 1 pass
    if path == "fused":
        return io
    streams = 2 * 4 * n                      # codes + shuffled words (u16 rt)
    if path == "reference" and direction == "compress":
        streams += 8 * n                     # int32 pre-quant stream as well
    return io + streams


def run(shape=(128, 128, 64), eb=1e-3, smoke=False):
    if smoke:
        shape = (32, 64, 32)
    f = jnp.asarray(make_field("smooth", shape, seed=3))
    rng = float(jnp.max(f) - jnp.min(f))
    eb_abs = quant.snap_eb(jnp.float32(eb * rng))
    nbytes = f.size * 4
    rows = []

    def add(name, secs, bytes_moved, hbm_model=None, measured=None):
        rows.append({"name": name, "us": secs * 1e6,
                     "gbps": gbps(bytes_moved, secs),
                     "hbm_model_bytes": hbm_model,
                     "measured_traffic": measured})

    # ---- pred-quant v1 (outlier path) vs v2 (branch-free saturating)
    q_v1 = jax.jit(lambda x: quant.dual_quantize(
        x, eb_abs, outlier_capacity=max(1, f.size // 64))[0])
    q_v2 = jax.jit(lambda x: quant.dual_quantize(x, eb_abs, outlier_capacity=0)[0])
    add("pred-quant-v1", timeit(q_v1, f), nbytes)
    add("pred-quant-v2", timeit(q_v2, f), nbytes)

    codes = _pad_flat(q_v2(f))
    n_blocks = codes.size // enc.BLOCK_WORDS

    # ---- bitshuffle+mark: two passes vs fused (real lowering on TPU)
    def v1(c):
        sh = shuffle.bitshuffle(c)
        return sh, enc.block_flags(sh)

    def v2(c):
        from repro.kernels import bitshuffle_flag as bsf
        return bsf.bitshuffle_flag(c.reshape(-1, shuffle.TILE),
                                   interpret=kops.backend_interpret())

    add("bitshuffle-mark-v1", timeit(jax.jit(v1), codes), 2 * codes.size)
    add("bitshuffle-mark-v2-fused", timeit(jax.jit(v2), codes), 2 * codes.size)

    # ---- encode phase 2 fed by v1-style codes (more nnz) vs v2 codes
    codes_v1 = _pad_flat(q_v1(f))
    sh_v1 = shuffle.bitshuffle(codes_v1)
    sh_v2 = shuffle.bitshuffle(codes)
    e = jax.jit(lambda s: enc.encode(s, capacity=n_blocks))
    nnz1, nnz2 = int(e(sh_v1)[2]), int(e(sh_v2)[2])
    add(f"prefix-sum-encode-v1(nnz={nnz1})", timeit(e, sh_v1), 2 * codes.size)
    add(f"prefix-sum-encode-v2(nnz={nnz2})", timeit(e, sh_v2), 2 * codes.size)

    # ---- whole-path staged vs fused megakernels (this PR's fusion claim);
    # one AOT compile per variant serves both the timing loop and the
    # memory_analysis traffic column
    for path in FZ_PATHS:
        cfg = fz_path_config(path, eb)
        comp = jax.jit(lambda x, cfg=cfg: fz.compress(x, cfg)) \
            .lower(f).compile()
        c = comp(f)
        out_bytes = int(c.wire_bytes())
        dec = jax.jit(lambda cc, cfg=cfg: fz.decompress(cc, cfg)) \
            .lower(c).compile()
        m_c = hlo_cost.compiled_memory_traffic(comp)
        m_d = hlo_cost.compiled_memory_traffic(dec)
        add(f"pipeline-compress-{path}", timeit(comp, f), nbytes,
            hbm_model_bytes(path, "compress", f.size, out_bytes),
            round(m_c["traffic_ratio"], 3))
        add(f"pipeline-decompress-{path}", timeit(dec, c), nbytes,
            hbm_model_bytes(path, "decompress", f.size, out_bytes),
            round(m_d["traffic_ratio"], 3))
    return rows


def main(smoke=False):
    rows = run(smoke=smoke)
    print("kernel,us_per_call,proxy_GBps,hbm_model_bytes,measured_traffic")
    for r in rows:
        model = "" if r["hbm_model_bytes"] is None else r["hbm_model_bytes"]
        meas = "" if r["measured_traffic"] is None else r["measured_traffic"]
        print(f"{r['name']},{r['us']:.0f},{r['gbps']:.3f},{model},{meas}")
    return rows


if __name__ == "__main__":
    main()
