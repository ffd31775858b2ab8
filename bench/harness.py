"""One run of one cell: set-up, the measured window, the check, the result.

The window drives the public wrappers ``fz.compress`` and ``fz.decompress``
with the kernels on (``use_kernels=True``, ``kernel_mode="auto"``). Every
variable of the snapshot is resident in HBM. In a closed loop it
compresses a variable, then decompresses that container, variable after
variable in an order drawn from the seed, and keeps the newest container of
each variable, as an in-memory user does. The window lasts ``--seconds``
and then to the end of the sweep over the variables, so every variable
counts equally often whatever the order: per-variable costs differ (decode
gathers by the data's zero blocks), and a window cut mid-sweep would let
the seed's order change the work. Each call ends in
``block_until_ready``; every moment of the window is charged to the
direction of the call it falls in. A reservoir drawn from the seed keeps
some calls' containers and reconstructions, which the plain reference
checks once the window has closed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from .xplane import DIRECTIONS, kernel_base

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_SECONDS = 10          # longest traced window
N_OPS = 10                  # entries of each breakdown list


class Refused(Exception):
    """The run cannot measure here (no chip, unknown device kind)."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[str, ...]
    per_layer: tuple[str, ...]
    units: dict[str, str]
    root: pathlib.Path


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    cfg_file = next(c["file"] for c in spec["configs"] if c["name"] == w["config"])

    def applies(m):
        return name in m.get("workloads", [name])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / cfg_file).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=tuple(m["name"] for m in spec["end_to_end"] if applies(m)),
        per_layer=tuple(m["name"] for m in spec["per_layer"] if applies(m)),
        units={m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]},
        root=root)


def reader(root: pathlib.Path, metric: str):
    """``read(ctx)`` of ``<root>/bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def fz_config(traffic: dict):
    from repro.core import fz
    return fz.FZConfig(eb=traffic["eb"], eb_mode=traffic["eb_mode"],
                       exact_outliers=traffic["exact_outliers"],
                       capacity_frac=traffic.get("capacity_frac", 1.0),
                       use_kernels=True, kernel_mode="auto")


def programs(x, cfg):
    """The compress and decompress programs compiled through the public
    API at ``x``'s shape and placement (``x`` may be a ShapeDtypeStruct)."""
    import jax
    from repro.core import fz
    comp = jax.jit(lambda d: fz.compress(d, cfg)).lower(x).compile()
    c_abs = jax.eval_shape(lambda d: fz.compress(d, cfg), x)
    sharding = getattr(x, "sharding", None)
    c_abs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                         c_abs)
    dec = jax.jit(lambda c: fz.decompress(c, cfg)).lower(c_abs).compile()
    return comp, dec


@dataclasses.dataclass
class Window:
    charged_s: dict[str, float]
    calls: dict[str, int]
    source_bytes: dict[str, int]
    used_bytes: int
    elapsed_s: float
    per_call_s: dict[str, list[float]]


@dataclasses.dataclass
class Context:
    """What a metric's ``read(ctx)`` sees."""
    setup_s: float
    window: Window
    workspace_bytes: int
    hbm_bytes_per_s: float | None
    kernels: dict[str, list[int]]
    trace: object | None = None          # xplane.Reduced of a traced run

    def idle_share(self, d: str) -> float | None:
        t = self.trace
        if t is None or t.span_s[d] <= 0 or t.busy_s[d] <= 0:
            return None
        return 100.0 * (1.0 - t.busy_s[d] / t.span_s[d])

    def direction_roofline(self, d: str) -> float | None:
        from . import roofline
        t = self.trace
        if t is None or t.busy_s[d] <= 0 or not self.hbm_bytes_per_s:
            return None
        per_call = self.window.source_bytes[d] / max(self.window.calls[d], 1)
        used = self.window.used_bytes / max(self.window.calls["compress"], 1)
        need = roofline.least_bytes(d, per_call, used) * t.calls[d]
        return 100.0 * need / self.hbm_bytes_per_s / t.busy_s[d]

    def kernel_roofline(self, kernel: str) -> float | None:
        t = self.trace
        if t is None or kernel not in self.kernels or not self.hbm_bytes_per_s:
            return None
        secs = n = 0
        for d in DIRECTIONS:
            for label, s in t.op_s[d].items():
                if kernel_base(label) == kernel:
                    secs += s
                    n += t.op_n[d][label]
        if n == 0 or secs <= 0:
            return None
        moved = sum(self.kernels[kernel]) / len(self.kernels[kernel])
        return 100.0 * moved / self.hbm_bytes_per_s / (secs / n)

    def xla_ms(self, d: str) -> float | None:
        t = self.trace
        if t is None or t.calls[d] == 0 or not t.op_s[d]:
            return None
        secs = sum(s for label, s in t.op_s[d].items()
                   if kernel_base(label) not in self.kernels)
        return 1e3 * secs / t.calls[d]


def run_window(fields, cfg, seconds: float, order, keep: int, rng):
    """The measured loop. Returns the Window and the kept calls."""
    import jax
    from repro.core import fz
    calls = dict.fromkeys(DIRECTIONS, 0)
    source = dict.fromkeys(DIRECTIONS, 0)
    sizes, kept = [], []
    per_call = {d: [] for d in DIRECTIONS}
    snapshot = [None] * len(fields)
    k = 0
    t0 = t = time.perf_counter()
    while True:
        i = order[k % len(order)]
        x = fields[i]
        with jax.profiler.TraceAnnotation("bench.compress"):
            c = jax.block_until_ready(fz.compress(x, cfg))
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.decompress"):
            rec = jax.block_until_ready(fz.decompress(c, cfg))
        t2 = time.perf_counter()
        per_call["compress"].append(t1 - t)
        per_call["decompress"].append(t2 - t1)
        t = t2
        for d in DIRECTIONS:
            calls[d] += 1
            source[d] += x.nbytes
        snapshot[i] = c
        sizes.append((c.nnz_blocks, c.n_outliers, c.shape))
        if len(kept) < keep:
            kept.append((i, c, rec))
        else:
            j = int(rng.integers(0, k + 1))
            if j < keep:
                kept[j] = (i, c, rec)
        del c, rec
        k += 1
        if t - t0 >= seconds and k % len(order) == 0:
            break
    used = used_bytes(sizes)
    del snapshot
    charged = {d: sum(v) for d, v in per_call.items()}
    return Window(charged, calls, source, used, t - t0, per_call), kept


def used_bytes(sizes) -> int:
    """Sum of the containers' ``used_bytes()``, worked out after the window."""
    import jax
    from repro.core import fz
    total = 0
    for shape in {s for _, _, s in sizes}:
        nnz, n_out = jax.device_get(([a for a, _, s in sizes if s == shape],
                                     [b for _, b, s in sizes if s == shape]))
        meta = fz.FZCompressed(bitflags=None, payload=None,
                               nnz_blocks=np.asarray(nnz, np.int32),
                               outlier_idx=None, outlier_val=None,
                               n_outliers=np.asarray(n_out, np.int32), eb_abs=None,
                               shape=shape, dtype_name="float32")
        total += int(np.sum(np.asarray(meta.used_bytes(), np.int64)))
    return total


def truncated(c):
    """``c`` with every payload block past ``nnz_blocks`` and every outlier
    slot past ``n_outliers`` cleared: what its used bytes hold."""
    import jax.numpy as jnp
    cap, k = c.payload.shape[1], c.outlier_idx.shape[0]
    live = jnp.arange(cap) < c.nnz_blocks
    slots = jnp.arange(k) < c.n_outliers
    return dataclasses.replace(
        c, payload=jnp.where(live[None, :], c.payload, 0).astype(c.payload.dtype),
        outlier_idx=jnp.where(slots, c.outlier_idx, c.n).astype(c.outlier_idx.dtype),
        outlier_val=jnp.where(slots, c.outlier_val, 0).astype(c.outlier_val.dtype))


def check(fields, kept, cfg, traffic: dict) -> dict:
    """The numbers ``correct`` compares, over the kept calls."""
    import jax
    from repro.core import fz
    from . import reference
    numbers = {"mismatch": 0, "max_err_over_eb": 0.0, "truncated_mismatch": 0}
    failed = 0
    hosts = {}
    for i, c, rec in kept:
        if i not in hosts:
            hosts[i] = np.asarray(jax.device_get(fields[i]))
        got = np.asarray(jax.device_get(rec))
        one = reference.compare(hosts[i], got, traffic["eb"], traffic["eb_mode"])
        again = np.asarray(jax.device_get(fz.decompress(truncated(c), cfg)))
        one["truncated_mismatch"] = int(np.count_nonzero(again != got)) \
            if again.shape == got.shape else got.size
        failed += not reference.verdict(one)
        numbers["mismatch"] += one["mismatch"]
        numbers["truncated_mismatch"] += one["truncated_mismatch"]
        numbers["max_err_over_eb"] = max(numbers["max_err_over_eb"],
                                         one["max_err_over_eb"])
    return {"numbers": numbers, "failed": failed, "checked": len(kept)}


def device_facts(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise Refused(f"needs {chips} TPU chip(s), JAX finds {len(devs)} "
                      f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def hbm_peak(root: pathlib.Path, kind: str, require: bool) -> float | None:
    table = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if kind not in table:
        if require:
            raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
        return None
    return float(table[kind]["hbm_bytes_per_s"])


def memory_peak() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, log=None) -> dict:
    """One run of ``cell``; returns the result object the CLI prints.
    A configuration of ``kind`` ``train`` runs ``bench/train.py``."""
    if cell.config.get("kind", "field") == "train":
        from . import train
        return train.run(cell, seed, seconds, trace, t_start, require_tpu, log)
    import jax
    from repro.core import fz
    from repro.launch.compile_cache import use_compile_cache
    from . import fields as gen
    from . import reference, roofline, xplane
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = device_facts(cell.chips, require_tpu)
    peak_bw = hbm_peak(cell.root, device["kind"], require_tpu)

    conf, traffic = cell.config, cell.traffic
    shape = tuple(conf["shape"])
    rng = np.random.default_rng([seed % 2**64, 1])
    fields = [jax.block_until_ready(gen.make(v, shape, seed, i))
              for i, v in enumerate(conf["variables"])]
    order = [int(i) for i in rng.permutation(len(fields))]
    cfg = fz_config(traffic)
    comp, dec = programs(fields[0], cfg)
    workspace = max(comp.memory_analysis().temp_size_in_bytes,
                    dec.memory_analysis().temp_size_in_bytes)
    kernels = roofline.kernel_bytes(comp.as_text())
    for name, moved in roofline.kernel_bytes(dec.as_text()).items():
        kernels.setdefault(name, []).extend(moved)
    del comp, dec
    # warm-up: the window's two calls at its one shape
    jax.block_until_ready(fz.decompress(fz.compress(fields[0], cfg), cfg))
    setup_s = time.perf_counter() - t_start
    log(f"{cell.name}: set-up {setup_s:.3f} s, {len(fields)} x {shape} float32, "
        f"workspace {workspace} B, kernels {sorted(kernels)}")

    gc.collect()
    trace_dir = None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window, kept = run_window(fields, cfg, seconds, order,
                                  int(conf.get("check_calls", 2)), rng)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak_mem = memory_peak()
    log(f"{cell.name}: window {window.elapsed_s:.3f} s, calls {window.calls}, "
        f"charged {window.charged_s}")
    for d, v in window.per_call_s.items():
        v = sorted(v)
        log(f"{cell.name}: {d} call s: min {v[0]:.6f} median {v[len(v) // 2]:.6f} "
            f"max {v[-1]:.6f}")

    reduced = None
    if trace:
        try:
            devs, host = xplane.read(xplane.find(trace_dir))
            reduced = xplane.reduce(devs, host, N_OPS)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = Context(setup_s=setup_s, window=window, workspace_bytes=workspace,
                  hbm_bytes_per_s=peak_bw, kernels=kernels, trace=reduced)
    metrics = {}
    for name in (cell.per_layer if trace else cell.end_to_end):
        value = reader(cell.root, name)(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell.units[name]}

    result = check(fields, kept, cfg, traffic)
    numbers = result["numbers"]
    correct = reference.verdict(numbers) and result["checked"] > 0
    for key, limit in reference.LIMITS.items():
        log(f"check {key} {numbers[key]!r} limit {limit!r}")
    out = {"correct": bool(correct),
           "attempted": window.calls["compress"] + window.calls["decompress"],
           "failed": result["failed"], "metrics": metrics,
           "device": {**device, "memory_peak_bytes": peak_mem}}
    if reduced is not None:
        out["device"]["busy_s"] = reduced.busy_total_s
        out["device"]["window_s"] = reduced.window_s
        out["breakdown"] = breakdown(reduced)
    out["checks"] = {k: {"value": numbers[k], "limit": lim}
                     for k, lim in reference.LIMITS.items()}
    return out


def breakdown(reduced) -> dict:
    ops = {f"{d}:{label}": s for d in DIRECTIONS
           for label, s in reduced.op_s[d].items()}
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:N_OPS]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in reduced.gaps[:N_OPS]]}
