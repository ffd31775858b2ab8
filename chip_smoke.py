#!/usr/bin/env python3
"""Smoke run of the FZ compressor's Pallas kernel path on a TPU.

    python chip_smoke.py                # one chip: three SDRBench-sized fields
    python chip_smoke.py --four-chips   # four chips: compressed gradient exchange

One chip: each field is generated from ``--seed`` (``repro.data.make_field``),
placed on the device, and run through the public wrappers ``fz.compress`` /
``fz.decompress`` with the kernels on — once in the paper's mode
(saturating codes, ``exact_outliers=False``) and once in the strict mode
(``exact_outliers=True``). Per field and mode it prints the implementation
dispatch chose, that the compiled compress and decompress programs contain a
Mosaic kernel (``tpu_custom_call``), compile and steady seconds (a smoke
timing, not a benchmark), the compression ratio, ``max_abs_err`` against
``eb_abs`` and the device's ``peak_bytes_in_use``. It fails unless the
kernel path's container and reconstruction are bit-identical to the jnp
reference (``use_kernels=False``) run on the same chip and data, and unless
the strict reconstruction is within ``eb_abs`` everywhere.

Four chips: a few yi-6b training steps (published widths, depth cut) through
``repro.launch.train``'s in-process path on a ``pods=2 x model=2`` mesh,
with FZ-compressed gradient exchange and then with the plain exchange. It
fails unless losses are finite and agree within ``LOSS_RTOL``, every device
holds a share of the state, and what the compiled step moves across pods
is the gradient containers (all-gathered) and no full-precision gradient in
the compressed run, and no all-gather in the plain run.

It runs in one process and needs a TPU: on any other platform it exits
non-zero before the first phase. The last line of its output is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# SDRBench shapes (name, make_field kind, shape), float32
FIELDS = (
    ("Nyx-like", "turbulent", (512, 512, 512)),
    ("Hurricane-ISABEL-like", "smooth", (100, 500, 500)),
    ("CESM-ATM-like", "smooth", (1800, 3600)),
)
EB = 1e-3                      # relative error bound of every field run
KERNEL_MARK = "tpu_custom_call"

# four-chip phase: yi-6b at its published widths, depth cut to one layer
# (~0.7B parameters with the 64000x4096 embeddings). Four replicas would
# each hold ~20 B/param (params, AdamW, error feedback: ~14 GB of 16 GB)
# before activations and the FZ pipeline, so two pods of two model shards.
# Three steps: the warmup gives step 0 a learning rate of 0, so the last
# loss is the one after the first update, before the chaotic first steps at
# the peak rate compound the difference between the runs.
FOUR_CHIP = dict(arch="yi-6b", layers=1, seq=512, batch=8, steps=3,
                 pods=2, model_parallel=2)
LOSS_RTOL = 1e-2               # compressed vs plain loss, see CHANGES.md


def _has_kernel(compiled_text: str) -> bool:
    return KERNEL_MARK in compiled_text


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _identical(a, b) -> bool:
    import jax
    import jax.numpy as jnp
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(la, lb))


def run_field(name: str, kind: str, shape: tuple, seed: int, log=print) -> dict:
    """Compress and decompress one field on the default device in both
    modes; raises on any failed check. Returns the per-mode facts."""
    import jax
    from repro.core import fz, metrics
    from repro.data import make_field

    x = jax.block_until_ready(jax.device_put(make_field(kind, shape, seed=seed)))
    log(f"{name}: {kind} {shape} float32, {x.nbytes / 2**20:.1f} MiB on "
        f"{x.devices().pop()}")
    paper = fz.FZConfig(eb=EB, eb_mode="rel", use_kernels=True,
                        exact_outliers=False)
    facts = {}
    for mode, cfg in (("paper", paper),
                      ("strict", dataclasses.replace(paper, exact_outliers=True))):
        tag = f"{name} [{mode}]"
        c_cfg = fz._resolved(cfg, "compress", x.size, "float32")
        d_cfg = fz._resolved(cfg, "decompress", x.size, "float32")
        for op, r in (("compress", c_cfg), ("decompress", d_cfg)):
            if not r.use_kernels:
                raise RuntimeError(f"{tag}: dispatch chose the jnp reference "
                                   f"for fz.{op}")
        log(f"{tag} impl: compress={c_cfg.kernel_mode} "
            f"decompress={d_cfg.kernel_mode}")

        # the programs the public wrappers run, compiled ahead of the calls
        t0 = time.perf_counter()
        comp_exe = fz._compress_jit.lower(x, c_cfg).compile()
        comp_s = time.perf_counter() - t0
        c_abs = jax.eval_shape(lambda d: fz._compress_jit(d, c_cfg), x)
        t0 = time.perf_counter()
        dec_exe = fz._decompress_jit.lower(c_abs, d_cfg).compile()
        dec_s = time.perf_counter() - t0
        kernels = {"compress": _has_kernel(comp_exe.as_text()),
                   "decompress": _has_kernel(dec_exe.as_text())}
        del comp_exe, dec_exe
        log(f"{tag} {KERNEL_MARK} in compiled programs: {kernels}")
        if not all(kernels.values()):
            raise RuntimeError(f"{tag}: no Mosaic kernel in {kernels}")

        c, first_c = _timed(lambda: fz.compress(x, cfg))
        c, steady_c = _timed(lambda: fz.compress(x, cfg))
        rec, first_d = _timed(lambda: fz.decompress(c, cfg))
        rec, steady_d = _timed(lambda: fz.decompress(c, cfg))
        log(f"{tag} smoke timing, not a benchmark: compile "
            f"{comp_s:.2f}s/{dec_s:.2f}s, first call {first_c:.3f}s/"
            f"{first_d:.3f}s, steady {steady_c:.4f}s/{steady_d:.4f}s "
            f"(compress/decompress)")

        ref_cfg = dataclasses.replace(cfg, use_kernels=False)
        ref_c = fz.compress(x, ref_cfg)
        ref_rec = fz.decompress(ref_c, ref_cfg)
        same = {"container": _identical(c, ref_c),
                "reconstruction": _identical(rec, ref_rec)}
        err = float(metrics.max_abs_err(x, rec))
        eb_abs = float(c.eb_abs)
        ratio = float(c.compression_ratio())
        log(f"{tag} ratio {ratio:.4f}  max_abs_err {err!r}  eb_abs {eb_abs!r}"
            f"  (err <= eb_abs: {err <= eb_abs})  n_outliers "
            f"{int(c.n_outliers)}")
        log(f"{tag} bit-identical to the reference on this device: {same}")
        log(f"{tag} peak_bytes_in_use {_peak_bytes()}")
        if not all(same.values()):
            raise RuntimeError(f"{tag}: kernel path differs from the "
                               f"reference: {same}")
        if mode == "strict" and not err <= eb_abs:
            raise RuntimeError(f"{tag}: max_abs_err {err} exceeds {eb_abs}")
        facts[mode] = {"impl": (c_cfg.kernel_mode, d_cfg.kernel_mode),
                       "kernels": kernels, "identical": same, "ratio": ratio,
                       "max_abs_err": err, "eb_abs": eb_abs}
        del c, rec, ref_c, ref_rec
    return facts


def _cross_pod_collectives(text: str, per_pod: int) -> set:
    """(collective, dtype, element count) of every collective across pods
    in a compiled program's text, by its output."""
    import math
    from repro.launch import hlo_cost
    out = set()
    for comp in hlo_cost.parse_computations(text).values():
        for op in comp.ops:
            base = op.opcode.removesuffix("-start")
            if base in hlo_cost.COLLECTIVES and \
                    hlo_cost.crosses_pod(op.rest, per_pod):
                shapes = hlo_cost._shape_dims(op.out_shape)
                if op.opcode == "all-gather-start":   # (operands, outputs)
                    shapes = shapes[len(shapes) // 2:]
                out |= {(base, dt, math.prod(dims)) for dt, dims in shapes}
    return out


def _hlo_dtype(dtype) -> str:
    """HLO's name of a numpy dtype (uint16 -> u16, int32 -> s32)."""
    import numpy as np
    d = np.dtype(dtype)
    return f"{'s' if d.kind == 'i' else d.kind}{8 * d.itemsize}"


# the v5e compiler turns an all-gather this small into an all-reduce (seen
# for the 8-byte eb_abs and the 128-byte bitflags of 4096-element leaves)
MIN_GATHER_BYTES = 1024


def _wire_model(params, gc, n_pods: int) -> dict:
    """What the compressed exchange may put across pods: the gathered
    (dtype, element count) of every container leaf and of those of at least
    ``MIN_GATHER_BYTES``, the all-gather bytes of the latter, and the
    all-reduce bytes it may cost (the leaves reduced exactly, the container
    leaves below that size, the loss; two bytes moved per byte reduced, as
    ``hlo_cost`` counts a ring)."""
    import jax
    import jax.numpy as jnp
    from repro.core import fz
    from repro.dist import compressed_allreduce as car
    gathers, large = set(), set()
    large_bytes, small_bytes, exact_bytes = 0, 0, 4        # the loss, f32
    for p in jax.tree.leaves(params):
        if not car._compressible(p.shape, p.dtype, gc):
            exact_bytes += 4 * p.size
            continue
        c = jax.eval_shape(lambda x: fz.compress(x, gc.fz_config()),
                           jax.ShapeDtypeStruct((p.size,), jnp.float32))
        for a in jax.tree.leaves(c):
            nbytes = n_pods * a.size * a.dtype.itemsize
            key = (_hlo_dtype(a.dtype), n_pods * a.size)
            gathers.add(key)
            if nbytes >= MIN_GATHER_BYTES:
                large.add(key)
                large_bytes += nbytes
            else:
                small_bytes += nbytes
    return {"gathers": gathers, "large": large,
            "gather_bytes": (large_bytes, large_bytes + small_bytes),
            "all_reduce_bytes": 2 * (exact_bytes + small_bytes)}


def _device_shares(log) -> list:
    import jax
    used = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices()]
    log(f"bytes_in_use per device: {used}")
    return used


def _check_step(label: str, trainer, gcfg, n_pods: int, log) -> list:
    """Failed checks of the compiled step's cross-pod collectives.
    Compressed: the gradient containers are all-gathered across pods, byte
    for byte, and nothing else is, and the cross-pod all-reduces are no more
    than the wire model allows (no full-precision gradient crosses the pod
    axis).
    Plain: nothing is all-gathered across pods."""
    import numpy as np
    from repro.launch import hlo_cost
    text = trainer.step_fn.lower(
        trainer.params, trainer.opt, trainer.err, np.int32(trainer.step),
        trainer._batch(trainer.step)).compile().as_text()
    per_pod = trainer.mesh.devices.size // n_pods
    detail = hlo_cost.analyze(text, devices_per_pod=per_pod)["collective_detail"]
    got = _cross_pod_collectives(text, per_pod)
    gathers = {(dt, n) for op, dt, n in got if op == "all-gather"}
    log(f"[{label}] cross-pod collectives (op, dtype, elements) in the "
        f"compiled step: {sorted(got)}")
    log(f"[{label}] collective bytes per device: {detail}")
    if not gcfg.enabled:
        return [f"[{label}] all-gathers across pods: {sorted(gathers)}"] \
            if gathers else []
    model = _wire_model(trainer.params, gcfg, n_pods)
    gathered = detail.get("all-gather@pod", 0.0)
    reduced = detail.get("all-reduce@pod", 0.0)
    lo, hi = model["gather_bytes"]
    log(f"[{label}] gradient containers (dtype, elements): "
        f"{sorted(model['gathers'])}; all-gather@pod allowed {lo}..{hi} "
        f"bytes, all-reduce@pod allowed {model['all_reduce_bytes']} bytes")
    failed = []
    if not lo <= gathered <= hi:
        failed.append(f"[{label}] {gathered:.0f} all-gather bytes across "
                      f"pods, the containers are {lo}..{hi}")
    if not model["large"] <= gathers:
        failed.append(f"[{label}] containers not all-gathered across pods: "
                      f"{sorted(model['large'] - gathers)}")
    if not gathers <= model["gathers"]:
        failed.append(f"[{label}] all-gathered across pods besides the "
                      f"containers: {sorted(gathers - model['gathers'])}")
    other = {op for op, _, _ in got} - {"all-gather", "all-reduce"}
    if other:
        failed.append(f"[{label}] other collectives across pods: {sorted(other)}")
    if reduced > model["all_reduce_bytes"]:
        failed.append(f"[{label}] {reduced:.0f} all-reduce bytes across pods, "
                      f"allowed {model['all_reduce_bytes']}")
    return failed


def run_four_chips(log=print) -> dict:
    """Compressed vs plain gradient exchange over four chips. Both runs go
    to the end, then every failed check is raised together. Returns the
    per-run losses."""
    import gc
    import jax
    import numpy as np
    from repro import configs
    from repro.dist import compressed_allreduce as car
    from repro.launch.train import build_trainer

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    spec = dict(FOUR_CHIP)
    steps = spec.pop("steps")
    runs = {"compressed": car.GradCompressionConfig(enabled=True, use_kernels=True),
            "plain": car.GradCompressionConfig(enabled=False)}
    losses, failed = {}, []
    for label, gcfg in runs.items():
        trainer, cfg = build_trainer(spec["arch"], layers=spec["layers"],
                                     seq=spec["seq"], batch=spec["batch"],
                                     steps=steps, pods=spec["pods"],
                                     model_parallel=spec["model_parallel"],
                                     grad_compress=gcfg)
        n_params = trainer.model.param_count()
        log(f"[{label}] {cfg.arch_id}: published widths (d_model {cfg.d_model}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab}), depth cut to "
            f"{cfg.n_layers} of {configs.get(spec['arch']).n_layers} layers, "
            f"{n_params / 1e9:.3f}B params, "
            f"mesh {dict(trainer.mesh.shape)}, seq {spec['seq']}, "
            f"batch {spec['batch']}")
        hist = trainer.run(steps)
        losses[label] = [m["loss"] for m in hist]
        log(f"[{label}] losses {losses[label]}  step seconds "
            f"{[round(m['seconds'], 3) for m in hist]}")
        used = _device_shares(log)
        if not all(np.isfinite(losses[label])):
            failed.append(f"[{label}] non-finite loss: {losses[label]}")
        if min(used) < 0.5 * max(used):
            failed.append(f"[{label}] state not spread over the devices: {used}")
        failed += _check_step(label, trainer, gcfg, spec["pods"], log)
        del trainer
        gc.collect()
    diff = [abs(a - b) / abs(b) for a, b in zip(losses["compressed"], losses["plain"])]
    log(f"relative loss difference compressed vs plain: {diff} (limit {LOSS_RTOL})")
    if not max(diff) <= LOSS_RTOL:
        failed.append(f"compressed run departs from plain: {diff}")
    if failed:
        raise RuntimeError("; ".join(failed))
    return losses


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0, help="field generator seed")
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip gradient-exchange phase")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.four_chips:
        run_four_chips()
    else:
        for name, kind, shape in FIELDS:
            run_field(name, kind, shape, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
