"""Exporter: Chrome ``trace_event`` JSON (Perfetto-loadable).

The span layer's event ring is exporter-agnostic; :func:`chrome_trace` /
:func:`write_chrome_trace` turn it into the Chrome trace format
(``chrome://tracing`` / https://ui.perfetto.dev): complete events
(``ph="X"``) with microsecond ``ts``/``dur``, one row per thread. Eager spans
export under category ``span``; per-compilation trace-time spans under
``jit-trace`` (they appear once, nested inside the eager span that
triggered compilation). Both take an explicit event list or default to the
live ring.
"""
from __future__ import annotations

import json

from . import spans as _spans

_META_KEYS = ("pid", "tid")


def chrome_trace(events: list[dict] | None = None,
                 metadata: dict | None = None) -> dict:
    """Build the ``{"traceEvents": [...]}`` document from span events."""
    events = _spans.events() if events is None else events
    out = []
    threads = {}
    for ev in events:
        out.append({
            "name": ev["name"], "cat": ev["cat"], "ph": "X",
            "ts": ev["ts"], "dur": ev["dur"],
            "pid": ev["pid"], "tid": ev["tid"],
            "args": {**ev.get("args", {}),
                     "depth": ev.get("depth", 0),
                     "parent": ev.get("parent")},
        })
        threads.setdefault((ev["pid"], ev["tid"]), len(threads))
    for (pid, tid), i in threads.items():
        out.append({"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                    "args": {"name": f"obs-{i}"}})
    doc = {"traceEvents": out, "displayTimeUnit": "ms"}
    if metadata:
        doc["otherData"] = metadata
    return doc


def write_chrome_trace(path: str, events: list[dict] | None = None,
                       metadata: dict | None = None) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(events, metadata), f)
    return path

