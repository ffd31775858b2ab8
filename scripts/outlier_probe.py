#!/usr/bin/env python
"""Outlier collection on the chip: device time at a chosen share of
saturating codes, and the compaction loop's trips per call in a cell.

    PYTHONPATH=src python scripts/outlier_probe.py field --shape 512,512,512 \\
        --frac 0 0.001 0.1 --seed 1 --reps 5
    PYTHONPATH=src python scripts/outlier_probe.py cell \\
        --workload nyx-512.strict --seed 1 --seconds 10

``field`` makes one Nyx-like field (the benchmark's generator for the first
variable of ``nyx-512``). For each share it picks the absolute bound at
which about that share of Lorenzo deltas saturates a code (0: the cells'
relative bound 1e-3), quantizes with the strict kernel, and times
``quant.collect_outliers`` alone on the residual, at the capacity
``FZConfig`` gives: device ms per call from the profile's program
executions, median over ``--reps``, with a checksum of the channel to
compare two trees by. ``cell`` runs the cell's traced window through the
benchmark harness and prints its metrics.

Both print the loop's trips per call where the program has the loop: the
executions of an instruction of the ``while`` body that
``collect_outliers`` runs under the ``outlier_chunk`` scope, over the calls.
One JSON line per case; needs a TPU.
"""
import argparse
import json
import pathlib
import re
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

WHILE = re.compile(r"^\s*%\S+ = .*\swhile\(.*\bbody=%([\w.\-]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def body_labels(text: str) -> set[str] | None:
    """Op labels of the compaction loop's body, or None without the loop."""
    from bench import stages
    bodies = set()
    for line in text.splitlines():
        m, name = WHILE.match(line), OP_NAME.search(line)
        if m and name and "collect_outliers" in name.group(1) \
                and "outlier_chunk" not in name.group(1):
            bodies.add(m.group(1))
    if not bodies:
        return None
    return {i.label for i in stages.parse(text).values() if i.comp in bodies}


def trips(labels: set[str] | None, op_n: dict[str, int], calls: int):
    if labels is None or not calls:
        return None
    return max((op_n.get(label, 0) for label in labels), default=0) / calls


def traced(fn, reps: int):
    """Run ``fn`` ``reps`` times under the profiler: (devices, host)."""
    import jax
    from bench import xplane
    with tempfile.TemporaryDirectory(prefix="outlier-probe-") as d:
        jax.profiler.start_trace(d)
        try:
            for _ in range(reps):
                jax.block_until_ready(fn())
        finally:
            jax.profiler.stop_trace()
        return xplane.read(xplane.find(d))


def field(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import fields, harness
    from bench.xplane import op_label
    from repro.core import fz, quant
    from repro.kernels import lorenzo_quant as lq

    shape = tuple(int(s) for s in args.shape.split(","))
    variable = harness.load_cell("nyx-512.strict").config["variables"][0]
    x = jax.block_until_ready(fields.make(variable, shape, args.seed, 0))
    k = fz.FZConfig().outlier_capacity(x.size)
    delta = np.abs(np.asarray(jax.jit(quant.lorenzo_delta)(x)).ravel())
    sample = delta[np.random.default_rng(args.seed).integers(0, delta.size, 1 << 20)]
    del delta
    quantize = jax.jit(lambda d, eb: lq.lorenzo_quant(d, eb, with_residual=True))

    def probe_collect(resid):
        return quant.collect_outliers(resid, k)

    collect = jax.jit(probe_collect)
    for frac in args.frac:
        if frac > 0:
            eb = float(np.quantile(sample, 1 - frac)) / (2 * quant.MAX_MAG)
        else:
            eb = fz.resolve_eb(x, fz.FZConfig(eb=1e-3, eb_mode="rel"))
        eb = quant.snap_eb(jnp.float32(eb))
        _, resid = jax.block_until_ready(quantize(x, eb))
        labels = body_labels(collect.lower(resid).compile().as_text())
        idx, val, n_over = jax.block_until_ready(collect(resid))
        devices, _ = traced(lambda: collect(resid), args.reps)
        (modules, ops), = devices.values()
        runs = [m for m in modules if "probe_collect" in m.name]
        ms = statistics.median(m.end - m.start for m in runs) * 1e3
        op_n = {}
        for op in ops:
            if any(m.start <= op.start <= m.end for m in runs):
                op_n[op_label(op.name)] = op_n.get(op_label(op.name), 0) + 1
        i64 = np.arange(1, k + 1, dtype=np.int64)
        print(json.dumps({
            "shape": shape, "frac_target": frac, "eb_abs": float(eb),
            "n_outliers": int(n_over), "frac": int(n_over) / x.size,
            "capacity": k, "collect_ms": ms, "runs": len(runs),
            "trips_per_call": trips(labels, op_n, len(runs)),
            "checksum": int(np.sum(i64 * np.asarray(idx, np.int64))
                            + 3 * np.sum(i64 * np.asarray(val, np.int64))),
            "device": jax.devices()[0].device_kind}), flush=True)
        del resid, idx, val


def cell(args) -> None:
    from bench import harness, stages, xplane
    t_start = time.perf_counter()
    read, kept = xplane.read, []

    def keep(path):   # harness.run deletes the trace once it has read it
        kept.append(read(path))
        return kept[-1]
    xplane.read = keep
    try:
        result = harness.run(harness.load_cell(args.workload, harness.ROOT),
                             args.seed, args.seconds, True, t_start)
    finally:
        xplane.read = read
    (devices, host), = kept
    reduced = xplane.reduce(devices, host)
    found = stages.texts(str(harness.ROOT), args.workload)
    labels = found and body_labels(found["compress"])
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": result["correct"], "calls": reduced.calls,
        "trips_per_call": trips(labels, reduced.op_n["compress"],
                                reduced.calls["compress"]),
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "device": result["device"]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    f = sub.add_parser("field")
    f.add_argument("--shape", default="512,512,512")
    f.add_argument("--frac", type=float, nargs="+", default=[0.0])
    f.add_argument("--seed", type=int, default=1)
    f.add_argument("--reps", type=int, default=5)
    c = sub.add_parser("cell")
    c.add_argument("--workload", required=True)
    c.add_argument("--seed", type=int, required=True)
    c.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("outlier_probe.py measures on a TPU; none found", file=sys.stderr)
        return 1
    field(args) if args.mode == "field" else cell(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
