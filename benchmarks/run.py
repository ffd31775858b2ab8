"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all, reduced sizes
    PYTHONPATH=src python -m benchmarks.run --only rate_distortion
    PYTHONPATH=src python -m benchmarks.run --only kvcache,overlap --smoke \
        --json-out BENCH_ci.json                       # the CI bench tier

Sections map to the paper:
    rate_distortion  -> Fig. 7   (bitrate vs PSNR, 4 compressors)
    throughput       -> Fig. 8/9 (compression/decompression, CPU-proxy)
    breakdown        -> Fig. 10  (per-kernel optimization effects)
    overall          -> Fig. 11  (overall data-transfer throughput model)
    integrations     -> §2.4 use cases in the framework (grads/KV/ckpt)
    kvcache          -> §2.4 in-memory: KV parking sweep + paged-pool trace
    overlap          -> §2.4 wire: barrier vs bucketed compressed reduce
    roofline         -> §Roofline table from the dry-run JSONs

``--smoke`` shrinks shapes/sweeps for CI; sections whose ``main`` accepts a
``smoke`` kwarg honour it, the rest run their defaults. ``--json-out``
collects every section's machine-readable return value (sections returning
None are recorded as null) into one document — CI writes ``BENCH_ci.json``
at the repo root and uploads it, the first datapoint of the perf
trajectory. The document also embeds a ``metrics_snapshot`` of the
repro.obs registry (dispatch counters, span histograms, sentinel state) so
the trajectory carries the telemetry of the run that produced it;
``--trace-out`` additionally dumps the span event ring as a Chrome trace.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time

SECTIONS = ("rate_distortion", "throughput", "breakdown", "overall",
            "integrations", "kvcache", "overlap", "roofline")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help=f"comma-separated subset of {', '.join(SECTIONS)}")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes / reduced sweeps (CI preset)")
    p.add_argument("--json-out", default=None,
                   help="write all sections' machine-readable results here")
    p.add_argument("--trace-out", default=None,
                   help="write the run's span events as Chrome trace JSON")
    args = p.parse_args()
    if args.only:
        todo = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in todo if s not in SECTIONS]
        if unknown:
            p.error(f"unknown sections {unknown}; choose from {SECTIONS}")
    else:
        todo = list(SECTIONS)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    results: dict[str, object] = {}
    for name in todo:
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["main"])
        fn = mod.main
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(fn).parameters:
            kwargs["smoke"] = True
        try:
            results[name] = fn(**kwargs)
        except Exception as e:  # keep the harness going; report the failure
            print(f"{name},FAILED,{e!r}", file=sys.stderr)
            raise
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    from repro import obs
    if args.json_out:
        snap = obs.snapshot()
        snap["sentinel_violations"] = obs.violations()
        doc = {"meta": {"smoke": args.smoke, "sections": todo,
                        "unix_time": int(time.time())},
               "sections": results,
               "metrics_snapshot": snap}
        with open(args.json_out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json_out}", flush=True)
    if args.trace_out:
        obs.write_chrome_trace(args.trace_out,
                               metadata={"smoke": args.smoke,
                                         "sections": todo})
        print(f"# wrote {args.trace_out}", flush=True)


if __name__ == "__main__":
    main()
