"""Faults planted in the timed path, to show that the check catches them.

Each fault wraps the program's ``(compress, decompress)`` pair and returns a
broken pair with the same signatures:

- ``altered``: an answer altered where it is produced: the first value of
  every reconstruction moves by two quantization steps;
- ``half_dropped``: half of the work left out: only the first half of each
  field (in flat order) is compressed, the rest is taken as zeros;
- ``stale``: a call that returns its state unchanged: compress hands back
  the container of the call before;
- ``short_count``: used bytes under-counted: each container claims half of
  its non-zero blocks, which would inflate the ratio.

The chip has no exchange between chips in these cells, so that fault does
not apply.
"""
from __future__ import annotations

import dataclasses


def altered(compress, decompress):
    def broken(c, cfg):
        rec = decompress(c, cfg)
        return rec.at[(0,) * rec.ndim].add(4.0 * c.eb_abs)
    return compress, broken


def half_dropped(compress, decompress):
    def broken(x, cfg):
        import jax.numpy as jnp
        flat = x.reshape(-1)
        keep = jnp.arange(flat.size) < flat.size // 2
        return compress(jnp.where(keep, flat, 0).reshape(x.shape), cfg)
    return broken, decompress


def stale(compress, decompress):
    last = []

    def broken(x, cfg):
        import jax
        c = compress(x, cfg)
        if not jax.core.trace_ctx.is_top_level():      # compiled, not called
            return c
        out = last[0] if last and last[0].shape == c.shape else c
        last[:] = [c]
        return out
    return broken, decompress


def short_count(compress, decompress):
    def broken(x, cfg):
        c = compress(x, cfg)
        return dataclasses.replace(c, nnz_blocks=c.nnz_blocks // 2)
    return broken, decompress


FAULTS = {f.__name__: f for f in (altered, half_dropped, stale, short_count)}
