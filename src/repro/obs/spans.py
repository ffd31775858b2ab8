"""Timed, nested spans that are safe inside ``jax.jit``.

``with span("kvpool.park", pages=n):`` does three things at once:

  * **metrics** — wall-clock duration lands in the log-bucketed histogram
    ``span_ms{span=<name>}`` and bumps ``span_calls{span=<name>}``;
  * **events** — a completed span appends one event to the bounded in-memory
    ring (``events()``), which ``obs.trace`` exports as Chrome
    ``trace_event`` JSON;
  * **profiler hooks** — the body runs under ``jax.named_scope(name)`` (the
    span name lands in XLA op metadata, so ``hlo_cost.analyze`` tag patterns
    and real XLA profiles see the same names) and, when running eagerly,
    ``jax.profiler.TraceAnnotation(name)`` (the span shows up in
    ``jax.profiler`` traces captured via ``--profile-dir`` on hardware).

jit discipline (load-bearing; pinned in tests/test_obs.py): a span entered
while a trace is in progress (``jax.core.trace_ctx.is_top_level()`` is False)
records **no runtime timing** — it contributes only the named_scope metadata
plus a single ``cat="jit-trace"`` ring event measuring how long *tracing*
that region took. Nothing is staged into the traced program: no ops, no
tracers captured, no Python state the jit cache key could see — so spans
compile to no-ops inside jit-traced regions, cannot cause retraces, and the
``span_traces{span=...}`` counter doubles as a retrace detector (it should
stick at the number of distinct compiled shapes).

Nesting is tracked with a ``contextvars`` stack: every event carries its
depth and parent span name, and the stack is restored on exit even under
reentrancy or exceptions.
"""
from __future__ import annotations

import contextvars
import functools
import os
import threading
import time
from collections import deque

import jax

from . import registry as _reg

_stack: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "obs_span_stack", default=())

DEFAULT_RING_CAPACITY = 65_536

_ring_lock = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_RING_CAPACITY)


def events() -> list[dict]:
    """Snapshot of the event ring, oldest first."""
    with _ring_lock:
        return list(_ring)


def clear_events() -> None:
    with _ring_lock:
        _ring.clear()


def ring_capacity() -> int:
    return _ring.maxlen


def set_ring_capacity(n: int) -> None:
    """Rebound the ring (keeps the newest events that still fit)."""
    global _ring
    with _ring_lock:
        _ring = deque(_ring, maxlen=int(n))


def current_stack() -> tuple:
    """The active span-name stack for this context (outermost first)."""
    return _stack.get()


def _clean_attrs(attrs: dict) -> dict:
    """JSON-safe args: scalars pass, everything else (incl. tracers) is
    stringified and truncated — never retains a reference to a tracer."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, (bool, int, float, str)) or v is None:
            out[k] = v
        else:
            out[k] = str(v)[:64]
    return out


def _record(name: str, cat: str, t0_us: float, dur_us: float,
            depth: int, parent: str | None, attrs: dict) -> None:
    ev = {"name": name, "cat": cat, "ts": t0_us, "dur": dur_us,
          "pid": os.getpid(), "tid": threading.get_ident(),
          "depth": depth, "parent": parent, "args": _clean_attrs(attrs)}
    with _ring_lock:
        _ring.append(ev)


class span:
    """Context manager / decorator for one named scope. Reentrant: the same
    instance can be entered recursively (each entry keeps its own frame)."""

    __slots__ = ("name", "attrs", "_frames")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._frames: list[tuple] = []

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(self.name, **self.attrs):
                return fn(*args, **kwargs)
        return wrapped

    def __enter__(self):
        if not _reg.enabled():
            self._frames.append(None)
            return self
        eager = jax.core.trace_ctx.is_top_level()
        stack = _stack.get()
        token = _stack.set(stack + (self.name,))
        scope = jax.named_scope(self.name)
        scope.__enter__()
        annot = None
        if eager:
            annot = jax.profiler.TraceAnnotation(self.name)
            annot.__enter__()
        parent = stack[-1] if stack else None
        self._frames.append((eager, token, scope, annot, parent,
                             len(stack), time.perf_counter_ns()))
        return self

    def __exit__(self, exc_type, exc, tb):
        frame = self._frames.pop()
        if frame is None:
            return False
        eager, token, scope, annot, parent, depth, t0 = frame
        dur_us = (time.perf_counter_ns() - t0) / 1e3
        if annot is not None:
            annot.__exit__(exc_type, exc, tb)
        scope.__exit__(exc_type, exc, tb)
        _stack.reset(token)
        if eager:
            _reg.counter("span_calls", span=self.name).inc()
            _reg.histogram("span_ms", span=self.name).observe(dur_us / 1e3)
            _record(self.name, "span", t0 / 1e3, dur_us, depth, parent,
                    self.attrs)
        else:
            # trace-time span: one event per compilation — a retrace detector
            # and the only (intentional) footprint inside jit
            _reg.counter("span_traces", span=self.name).inc()
            _record(self.name, "jit-trace", t0 / 1e3, dur_us, depth, parent,
                    self.attrs)
        return False
