#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workloads nyx-512.strict,nyx-512.paper \\
        --seeds 101,102,103 --fault-seeds 2

For each cell and seed it makes the cell's fields as a run does, sends the
run's number of calls (``check_calls``, variables drawn from the seed)
through the timed entry (``fz.compress`` then ``fz.decompress``) at the
cell's size, and prints, as JSON lines:

- ``program``: the numbers the check compares, for the program: the lower
  readings;
- ``control``: the same numbers for the plain reference computed in
  bfloat16 and put in the program's place;
- ``fault:<name>``: the numbers with a fault of ``faults.py`` planted in the
  program, on the first ``--fault-seeds`` seeds.

The last line sums them up: per number, the largest program reading and the
smallest reading of the control and of each fault. The benchmark's runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cell, seeds, fault_seeds: int, emit) -> dict:
    import jax
    import numpy as np
    from repro.core import fz
    from bench import faults, fields as gen, harness, reference

    conf, traffic = cell.config, cell.traffic
    cfg = harness.fz_config(traffic)
    shape = tuple(conf["shape"])
    summary: dict[str, dict] = {}

    def note(kind, seed, numbers):
        emit({"cell": cell.name, "seed": seed, "kind": kind, **numbers})
        best = summary.setdefault(kind, {})
        pick = max if kind == "program" else min
        for k, v in numbers.items():
            best[k] = pick(best.get(k, v), v)

    for n, seed in enumerate(seeds):
        xs = [jax.block_until_ready(gen.make(v, shape, seed, i))
              for i, v in enumerate(conf["variables"])]
        rng = np.random.default_rng([seed % 2**64, 2])
        picks = rng.choice(len(xs), size=min(int(conf.get("check_calls", 2)), len(xs)),
                           replace=False)
        kept = []
        for i in picks:
            c = fz.compress(xs[i], cfg)
            kept.append((int(i), c, jax.block_until_ready(fz.decompress(c, cfg))))
        note("program", seed, harness.check(xs, kept, cfg, traffic)["numbers"])
        del kept
        worst = {"mismatch": 0, "max_err_over_eb": 0.0}
        for i in picks:
            x = np.asarray(jax.device_get(xs[i]))
            one = reference.compare(x, reference.control(x, traffic["eb"],
                                                         traffic["eb_mode"]),
                                    traffic["eb"], traffic["eb_mode"])
            worst["mismatch"] += one["mismatch"]
            worst["max_err_over_eb"] = max(worst["max_err_over_eb"], one["max_err_over_eb"])
        note("control", seed, worst)
        if n < fault_seeds:
            i = int(picks[0])
            other = (i + 1) % len(xs)
            for name, fault in faults.FAULTS.items():
                comp, dec = fault(fz.compress, fz.decompress)
                for j in (other, i):
                    c = comp(xs[j], cfg)
                    rec = jax.block_until_ready(dec(c, cfg))
                note(f"fault:{name}", seed,
                     harness.check(xs, [(i, c, rec)], cfg, traffic)["numbers"])
                del c, rec
        del xs
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache
    import jax
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]

    def emit(obj):
        print(json.dumps(obj), flush=True)
    for name in args.workloads.split(","):
        cell = harness.load_cell(name, ROOT)
        harness.device_facts(cell.chips, require_tpu=True)
        emit({"cell": name, "summary": readings(cell, seeds, args.fault_seeds, emit)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
