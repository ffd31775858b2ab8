"""Barrier vs bucketed compressed-gradient reduce sweep (machine-readable).

The overlap claim (dist/bucketed_reduce.py) is structural — per-bucket
compress/all_gather/decompress regions issued in backward production order
give XLA's latency-hiding scheduler something to overlap — so this bench
measures the reduce hop itself over synthetic gradient trees on the fake
multi-device CPU mesh: bucket count x leaf-size mix x pod count, barrier vs
bucketed. On this box the wall clock reflects orchestration shape (region
count, per-region work), not DCN speed; the analytic wire bytes per
configuration ride along so the trajectory stays comparable when the same
sweep runs on real multi-pod hardware.

Runs its measurement in a subprocess with 8 fake XLA CPU devices (the main
benchmark process keeps the default single-device view, like
tests/test_dist.py). Every row is therefore CPU rehearsal output, labelled
``platform: "cpu-rehearsal"``, never a chip timing; on a TPU host the sweep
refuses to run, since the parent already holds the chip. Emits one JSON
document; ``benchmarks/run.py --json-out`` folds it into BENCH_ci.json, the
CI perf-trajectory artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

N_DEVICES = 8

# leaf-size mixes: elements per leaf of one pod's gradient tree. "uniform"
# is the homogeneous-layer case; "skewed" is the realistic embed-heavy tree
# (two dominant leaves + a tail of small ones) where bucketing decides
# whether the tail amortizes or the big leaves serialize.
MIXES = {
    "uniform": [1 << 14] * 8,
    "skewed": [1 << 16] * 2 + [1 << 12] * 8,
}
FULL_SCALE = 4                      # full mode: 4x the smoke element counts
PLATFORM = "cpu-rehearsal"          # label on every row: fake CPU devices


def _child(smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import timeit
    from repro.dist import bucketed_reduce as bkt
    from repro.launch.mesh import make_mesh
    from repro.dist.compressed_allreduce import (GradCompressionConfig,
                                                 init_error_state,
                                                 reduce_stacked)

    assert jax.device_count() >= N_DEVICES, jax.device_count()
    scale = 1 if smoke else FULL_SCALE
    pods_sweep = (2,) if smoke else (2, 4)
    bucket_sweep = (1 << 16,) if smoke else (1 << 15, 1 << 17, 1 << 20)
    iters = 3 if smoke else 5

    rows = []
    for pods in pods_sweep:
        mesh = make_mesh((pods, N_DEVICES // pods), ("pod", "data"))
        for mix_name, sizes in MIXES.items():
            rng = np.random.default_rng(0)
            g_stack = {f"leaf{i:02d}": jnp.asarray(
                np.cumsum(rng.standard_normal((pods, n * scale)), axis=1)
                .astype(np.float32) * 1e-3)
                for i, n in enumerate(sizes)}
            g_abs = jax.tree.map(
                lambda g: jax.ShapeDtypeStruct(g.shape[1:], g.dtype), g_stack)
            raw_mb = sum(4 * n * scale for n in sizes) / 1e6

            def measure(fn, err):
                jitted = jax.jit(fn)
                return timeit(jitted, g_stack, err, warmup=1, iters=iters)

            gc = GradCompressionConfig(enabled=True, min_leaf_size=1024)
            sec = measure(lambda g, e: reduce_stacked(g, e, gc, mesh),
                          init_error_state(g_abs, pods, gc))
            base = {"mix": mix_name, "pods": pods, "raw_mb": round(raw_mb, 3)}
            rows.append({**base, "mode": "barrier", "bucket_bytes": None,
                         "n_buckets": len(sizes), "seconds": sec,
                         "wire_mb": None})
            for bb in bucket_sweep:
                gcb = GradCompressionConfig(enabled=True, min_leaf_size=1024,
                                            overlap=True, bucket_bytes=bb)
                plan = bkt.assign_buckets(g_abs, gcb)
                sec = measure(
                    lambda g, e: bkt.reduce_stacked_bucketed(g, e, gcb, mesh,
                                                             plan=plan),
                    init_error_state(g_abs, pods, gcb))
                wire_mb = sum(b.wire_bytes for b in plan.buckets) / 1e6
                rows.append({**base, "mode": "bucketed", "bucket_bytes": bb,
                             "n_buckets": plan.n_buckets, "seconds": sec,
                             "wire_mb": round(wire_mb, 3)})
    rows = [{**row, "platform": PLATFORM} for row in rows]
    return {"rows": rows, "device_count": N_DEVICES, "smoke": smoke,
            "platform": PLATFORM}


def main(smoke: bool = False) -> dict:
    """Spawn the fake-device child, print a table, return the JSON dict."""
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "bench_overlap is a CPU rehearsal on 8 fake devices; on a TPU "
            "host its child would time the CPU under the chip's name")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    root = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(src), os.path.abspath(root)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, "-m", "benchmarks.bench_overlap", "--child"]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=os.path.abspath(root))
    if r.returncode != 0:
        raise RuntimeError(f"overlap child failed:\n{r.stdout[-2000:]}\n"
                           f"{r.stderr[-2000:]}")
    out = json.loads(r.stdout.splitlines()[-1])
    print(f"# {PLATFORM}: {N_DEVICES} fake CPU devices, not a chip timing")
    print("mix,pods,mode,bucket_bytes,n_buckets,raw_mb,wire_mb,ms")
    for row in out["rows"]:
        wire = "" if row["wire_mb"] is None else f'{row["wire_mb"]}'
        bb = "" if row["bucket_bytes"] is None else str(row["bucket_bytes"])
        print(f'{row["mix"]},{row["pods"]},{row["mode"]},{bb},'
              f'{row["n_buckets"]},{row["raw_mb"]},{wire},'
              f'{row["seconds"] * 1e3:.1f}')
    return out


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--child", action="store_true",
                   help="run the measurement in-process (expects fake devices)")
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args()
    if args.child:
        print(json.dumps(_child(args.smoke)))
    else:
        main(smoke=args.smoke)
