"""BENCH trajectory: append per-run summary rows, gate on regressions.

``scripts/ci.sh bench`` overwrites ``BENCH_ci.json`` every run — good for
"what does this tree do", useless for "what did the last ten PRs do". This
module compacts one bench document into a flat ``{key: value}`` summary row
and appends it to ``BENCH_history.jsonl`` (one JSON object per line, commit
the file to carry the trajectory), then soft-gates the new row against the
previous row *of the same smoke flag*: any tracked metric that moved more
than ``--threshold`` (default 25%) in its bad direction prints a warning,
and ``--strict`` turns warnings into a non-zero exit.

Tracked keys and their good direction:

  * ``throughput/<path>/<direction>_gbps``  (higher) — mean GB/s per FZ
    execution path, including the tuned ``auto`` path;
  * ``kvcache/decode/<name>_ms``            (lower)  — paged decode steps;
  * ``overlap/cpu-rehearsal/<mode>_s``      (lower)  — reduce wall time on
    8 fake CPU devices (never a chip timing);
  * ``rate_distortion/<kind>_cold_bitrate`` (lower)  — entropy-tier bits
    per element at the frontier.

The gate is *soft* by default because CI boxes differ: a >25% drop is worth
a look, not an automatic revert — the history line is the evidence either
way.

    python -m benchmarks.history BENCH_ci.json
    python -m benchmarks.history BENCH_ci.json --strict --threshold 0.3
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

DEFAULT_HISTORY = "BENCH_history.jsonl"
DEFAULT_THRESHOLD = 0.25


def _mean(vals) -> float | None:
    vals = [float(v) for v in vals]
    return sum(vals) / len(vals) if vals else None


def summarize(doc: dict) -> dict[str, dict]:
    """Compact one bench document into {key: {value, better}} metrics."""
    out: dict[str, dict] = {}

    def put(key: str, value, better: str) -> None:
        if value is not None:
            out[key] = {"value": float(value), "better": better}

    sections = doc.get("sections", {})
    thr = sections.get("throughput") or {}
    rows = thr.get("rows", [])
    for path in sorted({r["path"] for r in rows}):
        for direction in ("compress", "decompress"):
            sel = [r["gbps"] for r in rows
                   if r["path"] == path and r["direction"] == direction]
            put(f"throughput/{path}/{direction}_gbps", _mean(sel), "higher")
    kv = sections.get("kvcache") or {}
    for r in kv.get("decode_ms", []):
        if isinstance(r, dict) and "name" in r and "step_ms" in r:
            put(f"kvcache/decode/{r['name']}_ms", r["step_ms"], "lower")
    ov = sections.get("overlap") or {}
    platform = ov.get("platform", "cpu-rehearsal")
    for mode in sorted({r["mode"] for r in ov.get("rows", [])}):
        sel = [r["seconds"] for r in ov.get("rows", [])
               if r["mode"] == mode and "seconds" in r]
        put(f"overlap/{platform}/{mode}_s", _mean(sel), "lower")
    rd = sections.get("rate_distortion") or {}
    for kind in sorted({r["kind"] for r in rd.get("rows", [])}):
        sel = [r["fz_cold_bitrate"] for r in rd.get("rows", [])
               if r["kind"] == kind and "fz_cold_bitrate" in r]
        put(f"rate_distortion/{kind}_cold_bitrate", _mean(sel), "lower")
    return out


def gate(prev: dict, cur: dict, threshold: float) -> list[str]:
    """Regressions of ``cur`` vs ``prev`` (same-key, > threshold, bad way)."""
    warnings = []
    pm, cm = prev.get("metrics", {}), cur.get("metrics", {})
    for key, c in sorted(cm.items()):
        p = pm.get(key)
        if not p or p["value"] <= 0:
            continue
        rel = (c["value"] - p["value"]) / p["value"]
        drop = -rel if c["better"] == "higher" else rel
        if drop > threshold:
            warnings.append(
                f"{key}: {p['value']:.4g} -> {c['value']:.4g} "
                f"({drop:+.0%} worse than the previous "
                f"{'smoke' if cur.get('smoke') else 'full'} row)")
    return warnings


def append_and_gate(bench_json: str, history_path: str,
                    threshold: float = DEFAULT_THRESHOLD) -> tuple[dict, list[str]]:
    doc = json.loads(pathlib.Path(bench_json).read_text())
    meta = doc.get("meta", {})
    row = {"unix_time": meta.get("unix_time"),
           "smoke": bool(meta.get("smoke")),
           "sections": meta.get("sections", []),
           "metrics": summarize(doc)}
    hist = pathlib.Path(history_path)
    warnings: list[str] = []
    if hist.exists():
        prev = None
        for line in hist.read_text().splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                cand = json.loads(line)
            except json.JSONDecodeError:
                continue  # a mangled line must not block the trajectory
            if isinstance(cand, dict) and cand.get("smoke") == row["smoke"]:
                prev = cand
        if prev is not None:
            warnings = gate(prev, row, threshold)
    with hist.open("a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return row, warnings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.history",
        description="append a bench summary row and soft-gate regressions")
    ap.add_argument("bench_json", help="BENCH_ci.json from benchmarks.run")
    ap.add_argument("--history", default=DEFAULT_HISTORY)
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative drop that counts as a regression")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero on regressions (default: warn only)")
    args = ap.parse_args(argv)
    row, warnings = append_and_gate(args.bench_json, args.history,
                                    args.threshold)
    print(f"history: appended {len(row['metrics'])} metric(s) to "
          f"{args.history} (smoke={row['smoke']})")
    for w in warnings:
        print(f"history: REGRESSION {w}", file=sys.stderr)
    if warnings and args.strict:
        return 1
    if not warnings:
        print("history: no regressions vs the previous comparable row")
    return 0


if __name__ == "__main__":
    sys.exit(main())
