"""Near-zero-overhead tuned dispatch: cached winner, else backend fallback.

This is the hot-path half of ``repro.tune``: ``fz.compress`` (and the
kvpool/engine/dist sites) resolve ``kernel_mode="auto"`` here on every
*eager* entry, so the lookup must cost a dict probe, not a file read. The
persistent cache is loaded once per process (and re-read only when the
tuner writes a new winner via :func:`invalidate_memo`), and resolutions are
memoized per ``(op, bucket, dtype)``.

Backend-aware routing (see also the ``core/fz.py`` module docstring):

  * ``tpu``: a fixed rule on what the code can observe, the element count
    (:func:`tpu_fz_impl`). The tuning cache is not consulted and the jnp
    reference is never chosen: on the chip a kernel request runs kernels,
    or fails loudly.
  * ``interpret`` / ``gpu`` (kernels interpret-executed today): the cached
    winner if any, else staged before fused, reference last —
    ``BENCH_ci.json`` measures fused compress ~4x *slower* than staged
    under the Pallas interpreter, which executes the megakernel's
    sequential grid in Python.

Untuned ``decode_attention`` keeps the kernel path — that request is
explicit (``use_kernels=True``) and kernel-vs-jnp parity is pinned; the
cache only *overrides* it where the jnp oracle measures faster.

Counters (gated on ``jax.core.trace_ctx.is_top_level()`` so retraces are never
tallied): ``tune_cache{result=hit|miss, site=dispatch}`` and
``tune_selected{op=..., impl=..., site=dispatch}``.
"""
from __future__ import annotations

import jax

from repro import obs

from . import registry
from .cache import TuneCache, cache_key, shape_bucket

# per-backend static ordering when no cache entry exists (most-preferred
# first); "gpu" mirrors "interpret" until real Triton lowering is measured.
# A TPU is routed by tpu_fz_impl instead.
FZ_FALLBACK = {
    "interpret": ("staged", "fused", "reference"),
    "gpu": ("staged", "fused", "reference"),
}

# Largest element count the fused megakernels are routed to on a TPU. It is
# 0 because the v5e compiler refuses both megakernels at every size tried,
# 4096 elements up to 512^3: "Cannot store scalars to VMEM"
# (fused_compress, fused_shuffle_encode) and an unimplemented in-kernel
# `cumsum` (fused_decode). tests/test_tpu_compile.py pins those refusals and
# the staged kernels' compiles at the SDRBench shapes, so raising this limit
# starts with a megakernel that compiles there.
TPU_FUSED_MAX_ELEMS = 0

_cache: TuneCache | None = None
_memo: dict[tuple[str, int, str], tuple[str, str]] = {}


def backend() -> str:
    """Registry backend label for the current jax default backend."""
    b = jax.default_backend()
    if b == "tpu":
        return "tpu"
    if b in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "interpret"


def arch() -> str:
    """Device kind the measurements were taken on (part of the cache key)."""
    return jax.devices()[0].device_kind.replace(" ", "_").replace("|", "_")


def active_cache() -> TuneCache:
    """The process-wide cache, loaded lazily from the default path."""
    global _cache
    if _cache is None:
        _cache = TuneCache().load()
    return _cache


def configure(path=None) -> TuneCache:
    """Point the process at a specific cache file (tests, CLI --cache)."""
    global _cache
    _cache = TuneCache(path).load()
    _memo.clear()
    return _cache


def reset() -> None:
    """Drop the loaded cache and memo (next lookup reloads from disk)."""
    global _cache
    _cache = None
    _memo.clear()


def invalidate_memo() -> None:
    """Called by the tuner after writing a winner so dispatch sees it."""
    _memo.clear()


def _count(result: str, op: str, impl: str) -> None:
    if not jax.core.trace_ctx.is_top_level():
        return
    obs.counter("tune_cache", result=result, site="dispatch").inc()
    obs.counter("tune_selected", op=op, impl=impl, site="dispatch").inc()


def _resolve(op: str, n: int, dtype: str, fallback_impl: str) -> str:
    memo_key = (op, shape_bucket(n), dtype)
    cached = _memo.get(memo_key)
    if cached is None:
        entry = active_cache().get(cache_key(backend(), op, n, dtype, arch()))
        if entry is not None:
            cached = (entry["impl"], "hit")
        else:
            cached = (fallback_impl, "miss")
        _memo[memo_key] = cached
    impl, result = cached
    _count(result, op, impl)
    return impl


def tpu_fz_impl(n: int) -> str:
    """The TPU routing rule for an FZ op over ``n`` elements."""
    return "fused" if n <= TPU_FUSED_MAX_ELEMS else "staged"


def fz_fallback_mode(n: int) -> str:
    """Kernel path for an untuned "auto" config over ``n`` elements
    ("staged" or "fused"): the TPU rule, else the first kernel of the
    backend's static ordering."""
    b = backend()
    if b == "tpu":
        return tpu_fz_impl(n)
    for impl in FZ_FALLBACK.get(b, FZ_FALLBACK["interpret"]):
        if impl != "reference":
            return impl
    return "staged"


def resolve_fz(direction: str, n: int, dtype: str) -> str:
    """Winning impl for ``fz.compress``/``fz.decompress`` at this workload:
    ``"reference" | "staged" | "fused"`` (never ``"reference"`` on a TPU).
    ``direction`` is "compress" or "decompress"."""
    op = f"fz.{direction}"
    b = backend()
    if b == "tpu":
        impl = tpu_fz_impl(n)
        if not any(c.impl == impl for c in registry.candidates(op, backend=b)):
            raise RuntimeError(
                f"no {impl!r} kernel registered for {op} on TPU; a kernel "
                f"request on the chip never falls back to the jnp reference")
        _count("rule", op, impl)
        return impl
    fallback = next(
        (impl for impl in FZ_FALLBACK.get(b, FZ_FALLBACK["interpret"])
         if any(c.impl == impl for c in registry.candidates(op, backend=b))),
        "reference")
    return _resolve(op, n, dtype, fallback)


def decode_attention_impl(n: int, dtype: str) -> str:
    """Winning impl for decode attention at a per-sequence cache of ``n``
    elements: ``"kernel" | "jnp"``. Untuned default stays "kernel" — the
    caller asked for kernels and parity is pinned; off a TPU the cache only
    overrides where the oracle measured faster. On a TPU it is always the
    kernel: a kernel request on the chip never runs the jnp oracle."""
    if backend() == "tpu":
        return "kernel"
    return _resolve("decode_attention", n, str(dtype), "kernel")
