#!/usr/bin/env python3
"""Readings that the limits of a training cell's ``correct`` are set from.

    python3 bench/train_control.py --workload yi-6b-dp4.fz \\
        --seeds 101,102,103 --control-seeds 3

In one process, for each seed it gives the cell's trainer the seed's
weights and rows, drives it through the first ``checked_steps`` steps of
its own loop as a run's set-up does, frees its state, runs the reference,
and prints JSON lines:

- ``program``: the numbers the check compares, for the program: the lower
  readings;
- ``control``: the same numbers for the reference computed from float8
  operands (``train_reference``, ``precision="fp8"``) put in the program's
  place, on the first ``--control-seeds`` seeds;
- ``fault:<name>``: a fault of ``train_reference.FAULTS`` planted in the
  reference put in the program's place, on the same seeds. A step that
  returns its state unchanged reads 1 on ``grad_norm_gap`` and
  ``change_norm_gap`` by their measure and needs no run.

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


def readings(cell, seeds, control_seeds: int, devices, emit) -> dict:
    import numpy as np
    from bench import train, train_reference as reference
    conf, traffic = cell.config, cell.traffic
    checked = int(traffic["checked_steps"])
    summary: dict[str, dict] = {}

    def note(kind, seed, numbers, **extra):
        emit({"cell": cell.name, "seed": seed, "kind": kind, **numbers, **extra})
        best = summary.setdefault(kind, {})
        pick = max if kind == "program" else min
        for k, v in numbers.items():
            best[k] = pick(best.get(k, v), v)

    trainer = None
    for n, seed in enumerate(seeds):
        if trainer is None:
            trainer = train.build(conf, traffic, seed)
        else:
            train.seed_state(trainer, conf, traffic, seed)
        prog = train.first_steps(trainer, conf, seed, checked)
        trainer.params = trainer.opt = trainer.err = None
        ref = reference.readings(conf, traffic, seed, devices)
        note("program", seed, train.compare(prog, ref),
             **{f"{k}.{side}": np.asarray(r[k]).tolist()
                for side, r in (("program", prog), ("reference", ref))
                for k in ("losses", "grad_norms", "change_norms")})
        if n < control_seeds:
            ctl = reference.readings(conf, traffic, seed, devices, precision="fp8")
            note("control", seed, train.compare(ctl, ref))
            for fault in reference.FAULTS:
                got = reference.readings(conf, traffic, seed, devices, fault=fault)
                note(f"fault:{fault}", seed, train.compare(got, ref))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload, ROOT)
    harness.device_facts(cell.chips, require_tpu=True)
    seeds = [int(s) for s in args.seeds.split(",")]

    def emit(obj):
        print(json.dumps(obj), flush=True)
    emit({"cell": cell.name, "summary": readings(cell, seeds, args.control_seeds,
                                                 jax.devices()[:cell.chips], emit)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
