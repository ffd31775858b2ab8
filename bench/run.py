#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU this process is started on.

    python3 bench/run.py --workload nyx-512.strict --seed 7 --seconds 30 --trace 0

Run from the root of a checkout. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` (as JAX reports it), with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number the check compared beside its limit. The last
lines of standard error give the same numbers. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)          # bench/ itself is no top-level package root
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload, ROOT)
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
