"""FZ public API: jit-safe error-bounded lossy (de)compression containers.

Pipeline (paper Fig. 1):  optimized dual-quantization -> bitshuffle ->
zero-block encoding. All stages are fixed-shape jnp programs, so a compressed
tensor is an ordinary pytree that can flow through jit / shard_map /
collectives — this is what makes the compressor a first-class distributed
feature (gradient compression, KV-cache pages, checkpoint payloads).

Three execution paths, selected by ``FZConfig.use_kernels`` /
``FZConfig.kernel_mode``:

  * ``use_kernels=False`` — pure-jnp reference (core.quant/shuffle/encode),
    the oracle everything else is pinned against;
  * ``use_kernels=True, kernel_mode="staged"`` — the per-stage Pallas kernels
    (fused quant kernel, fused shuffle+flag kernel, XLA scan/scatter/gather
    phase-2 epilogue); the u16 code stream round-trips HBM between launches.
    Retained as a second oracle next to the reference;
  * ``use_kernels=True, kernel_mode="fused"`` — one compress megakernel and
    one decompress megakernel
    (kernels/fused_compress.py, kernels/fused_decode.py): quant + Lorenzo +
    bitshuffle + flagging + phase-2 compaction in a single launch (and the
    full inverse pipeline in another), with the code stream, shuffled words
    and payload offsets living entirely in VMEM/SMEM scratch. With the
    exact-outlier channel on, quantization runs as the staged kernel, which
    also writes the residuals, and the rest stays fused (see
    kernels/ops.py:fused_compress_stages for the reason).

All three produce bit-identical containers and reconstructions (pinned by
the three-way property suite in tests/test_fz_properties.py).

``kernel_mode="auto"`` (the default) resolves to one of the concrete paths
per workload via :mod:`repro.tune`. On a TPU a fixed rule on the element
count decides (``tune.dispatch.tpu_fz_impl``): kernels always, the fused
megakernels only up to ``TPU_FUSED_MAX_ELEMS`` — today 0, since the v5e
compiler refuses them — and never the jnp reference. Elsewhere it is the
persistently cached, parity-gated winner of an empirical sweep when one
exists for this ``(backend, op, shape-bucket, dtype, arch)``, else a static
ordering: under the Pallas interpreter the fused megakernels' sequential
grid executes in Python and ``BENCH_ci.json`` measures fused compress ~4x
*slower* than staged, so interpret-class backends take staged before fused.
Resolution happens in the *eager* public wrappers before the jitted inner
is entered, so every jit cache key is a concrete resolved config — a later
cache update can never leave a stale "auto" trace behind.

Telemetry: the public entry points are thin eager wrappers over the jitted
pipelines. When called eagerly each opens an ``fz.<op>`` span around all of
its host work (config resolution, the jit dispatch, the
``fz_dispatches{op=...,path=...}`` counter in :mod:`repro.obs`); when
reached from inside an enclosing trace they fall straight through to the
jitted inner (a trace is not a dispatch — counting there would tally
compilations, not work). Inside the staged and reference programs every
operation sits under exactly one innermost ``fz.stage.<name>`` scope, which
lands in the compiled program's op metadata and nowhere else: compress runs
``resolve_eb``, ``quantize`` (holding ``collect_outliers``) and
``shuffle_encode`` (holding ``compact_blocks``); decompress runs
``decode_blocks``, ``unshuffle`` and ``dequantize``. :func:`lowered` gives
the program a wrapper dispatches, so a profile's device ops can be joined to
their stages. The batched page entry points
(``compress_batch_with_eb`` / ``decompress_batch``) live here for the same
reason: one vmapped launch is one dispatch, and keeping the counting next to
the launch is what lets the kvpool's ``decompress_dispatches`` stat and the
fz-level dispatch counter agree exactly. ``decompress_unmetered`` bypasses
the counters — it exists for the error-bound sentinels, whose sampled
roundtrip checks must not pollute the dispatch accounting they audit.
"""
from __future__ import annotations

import dataclasses
import struct
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import encode as enc
from . import entropy as ent
from . import quant, shuffle


@dataclasses.dataclass(frozen=True)
class FZConfig:
    """Static compressor configuration (hashable; safe as a jit static arg)."""
    eb: float = 1e-3               # error bound (absolute, or relative to range)
    eb_mode: str = "rel"           # "abs" | "rel" (relative to value range, paper-style)
    code_mode: str = "sign_mag"    # "sign_mag" (paper) | "zigzag" (beyond-paper)
    capacity_frac: float = 1.0     # payload capacity as fraction of worst case
    outlier_frac: float = 1 / 256  # exact-outlier side-channel capacity fraction
    exact_outliers: bool = True    # strict error bound (beyond-paper); False = paper-faithful
    use_kernels: bool = False      # route hot stages through Pallas kernels
    kernel_mode: str = "auto"      # "auto" tuned | "fused" megakernels | "staged"

    def __post_init__(self):
        if self.kernel_mode not in ("auto", "fused", "staged"):
            raise ValueError(f"unknown kernel_mode {self.kernel_mode!r}")

    def payload_capacity(self, n: int) -> int:
        n_blocks = self.n_blocks(n)
        return max(1, int(n_blocks * self.capacity_frac))

    def outlier_capacity(self, n: int) -> int:
        if not self.exact_outliers:
            return 0
        return max(1, int(n * self.outlier_frac))

    @staticmethod
    def padded_n(n: int) -> int:
        return (n + shuffle.TILE - 1) // shuffle.TILE * shuffle.TILE

    @classmethod
    def n_blocks(cls, n: int) -> int:
        return cls.padded_n(n) // enc.BLOCK_WORDS


@partial(jax.tree_util.register_dataclass,
         data_fields=("bitflags", "payload", "nnz_blocks", "outlier_idx",
                      "outlier_val", "n_outliers", "eb_abs"),
         meta_fields=("shape", "dtype_name"))
@dataclasses.dataclass
class FZCompressed:
    """Fixed-shape compressed tensor (a pytree; jit/collective-safe)."""
    bitflags: jax.Array        # u32[ceil(n_blocks/32)]
    payload: jax.Array         # u16[8, capacity] — column k is stored block k
    nnz_blocks: jax.Array      # i32[] — used payload prefix
    outlier_idx: jax.Array     # i32[K]
    outlier_val: jax.Array     # i32[K]
    n_outliers: jax.Array      # i32[]
    eb_abs: jax.Array          # f32[] — resolved absolute error bound
    shape: tuple[int, ...]     # static: original tensor shape
    dtype_name: str            # static: original dtype

    @property
    def n(self) -> int:
        out = 1
        for s in self.shape:
            out *= s
        return out

    def used_bytes(self) -> jax.Array:
        return enc.used_bytes(FZConfig.n_blocks(self.n), self.nnz_blocks, self.n_outliers)

    def raw_bytes(self) -> int:
        return self.n * jnp.dtype(self.dtype_name).itemsize

    def compression_ratio(self) -> jax.Array:
        return self.raw_bytes() / self.used_bytes().astype(jnp.float32)

    def wire_bytes(self) -> int:
        """Bytes actually moved if this container crosses a link (capacity-sized)."""
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(self)))


def resolve_eb(data: jax.Array, cfg: FZConfig) -> jax.Array:
    """The container's absolute bound: the configured one, snapped down to
    8 significant bits (``quant.snap_eb``) so that it holds without an f32
    rounding allowance."""
    if cfg.eb_mode == "abs":
        return quant.snap_eb(jnp.float32(cfg.eb))
    if cfg.eb_mode == "rel":
        rng = jnp.max(data) - jnp.min(data)
        # floor at eb*max|x|: keeps constant fields finite (range == 0) and
        # bounds pre-quantization codes by 1/(2*eb) — no int32 overflow
        maxabs = jnp.max(jnp.abs(data))
        eb = cfg.eb * jnp.maximum(rng, maxabs).astype(jnp.float32)
        return quant.snap_eb(jnp.maximum(eb, jnp.float32(1e-30)))
    raise ValueError(f"unknown eb_mode {cfg.eb_mode!r}")


def _fused(cfg: FZConfig) -> bool:
    return cfg.use_kernels and cfg.kernel_mode == "fused"


def _resolved(cfg: FZConfig, direction: str, n: int, dtype_name: str) -> FZConfig:
    """Resolve ``kernel_mode="auto"`` to a concrete execution path.

    Called by every eager public entry point *before* the jitted inner, so
    jit caches key on the resolved config. :func:`repro.tune.resolve_fz`
    applies the TPU size rule, or elsewhere the cached winner or the static
    staged-before-fused ordering. See the module docstring.
    """
    if not (cfg.use_kernels and cfg.kernel_mode == "auto"):
        return cfg
    from repro import tune
    impl = tune.resolve_fz(direction, n, dtype_name)
    if impl == "reference":
        return dataclasses.replace(cfg, use_kernels=False, kernel_mode="staged")
    return dataclasses.replace(cfg, kernel_mode=impl)


def _static_auto(cfg: FZConfig, n: int) -> FZConfig:
    """Last-ditch "auto" resolution for internal callers that bypass the
    public wrappers (direct ``_*_jit`` use): static backend fallback only —
    deterministic per backend and size, no cache lookup, so a jit trace
    keyed on an "auto" config can never go stale against a cache update."""
    if not (cfg.use_kernels and cfg.kernel_mode == "auto"):
        return cfg
    from repro.tune import dispatch
    return dataclasses.replace(cfg, kernel_mode=dispatch.fz_fallback_mode(n))


def _stages(cfg: FZConfig):
    """Pick reference vs staged-Pallas implementations of the hot stages.

    The fused megakernel path doesn't decompose into these three stages —
    ``_compress_core`` / ``decompress`` route it wholesale via ``_fused``.
    The ``fz.stage.*`` scopes are opened by the callers, for either choice.
    """
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        return kops.lorenzo_quantize, kops.bitshuffle_flag_encode, kops.bitunshuffle
    def ref_shuffle_encode(codes_flat, *, capacity):
        return enc.encode(shuffle.bitshuffle(codes_flat), capacity=capacity)
    def ref_unshuffle(words):
        return shuffle.bitunshuffle(words.T.reshape(-1))
    return quant.dual_quantize, ref_shuffle_encode, ref_unshuffle


def _source_dtype_name(data: jax.Array) -> str:
    """Dtype the container's byte accounting is charged against.

    Captured from the *incoming* array before the pipeline's internal
    float32 cast, so a bfloat16 KV page reports ``raw_bytes() == n * 2``
    (not the 2x-inflated float32 figure) and ``compression_ratio()`` is
    honest. Non-float inputs are charged as the float32 they become.
    """
    return str(data.dtype) if jnp.issubdtype(data.dtype, jnp.floating) \
        else "float32"


def _path(cfg: FZConfig) -> str:
    """Execution-path label for metrics/spans."""
    if _fused(cfg):
        return "fused"
    return "staged" if cfg.use_kernels else "reference"


def _dispatch(op: str, span: str, jitted, args: tuple, cfg: FZConfig, size: int,
              dtype_name: str, **attrs):
    """``jitted(*args, cfg)`` at the resolved ``cfg``. Eagerly, one launch is
    one dispatch: the ``span`` holds the resolution, the launch and the
    ``fz_dispatches`` count. Inside an enclosing trace it is neither timed
    nor counted."""
    if not jax.core.trace_ctx.is_top_level():
        return jitted(*args, _resolved(cfg, op, size, dtype_name))
    with obs.span(span, **attrs):
        cfg = _resolved(cfg, op, size, dtype_name)
        out = jitted(*args, cfg)
        obs.counter("fz_dispatches", op=op, path=_path(cfg)).inc()
    return out


@partial(jax.jit, static_argnames=("cfg",))
def _compress_jit(data: jax.Array, cfg: FZConfig) -> FZCompressed:
    cfg = _static_auto(cfg, data.size)
    dtype_name = _source_dtype_name(data)
    with obs.span("fz.stage.resolve_eb"):
        data = data.astype(jnp.float32)
        eb = resolve_eb(data, cfg)
    return _compress_core(data, eb, cfg, dtype_name)


def compress(data: jax.Array, cfg: FZConfig) -> FZCompressed:
    """Error-bounded lossy compression of a 1-3D float array.

    The source dtype is recorded in the container (``dtype_name``) for byte
    accounting; the quantization math itself always runs in float32.
    """
    n = int(data.size)
    return _dispatch("compress", "fz.compress", _compress_jit, (data,), cfg, n,
                     _source_dtype_name(data), n=n)


@partial(jax.jit, static_argnames=("cfg",))
def _compress_with_eb_jit(data: jax.Array, eb_abs: jax.Array,
                          cfg: FZConfig) -> FZCompressed:
    cfg = _static_auto(cfg, data.size)
    dtype_name = _source_dtype_name(data)
    with obs.span("fz.stage.resolve_eb"):
        data = data.astype(jnp.float32)
        eb = quant.snap_eb(jnp.maximum(jnp.asarray(eb_abs, jnp.float32),
                                       jnp.float32(1e-30)))
    return _compress_core(data, eb, cfg, dtype_name)


def compress_with_eb(data: jax.Array, eb_abs: jax.Array, cfg: FZConfig) -> FZCompressed:
    """Compress with a caller-supplied *absolute* error bound (traced scalar).

    Page-granular compression (serve/kvpool) needs every chunk of a tensor
    quantized against one shared bound: the reconstruction grid is then
    ``round(x / 2eb) * 2eb`` independent of how the tensor was chunked, so
    per-page roundtrips are bit-identical to a whole-tensor roundtrip. Because
    ``eb_abs`` is traced (not baked into ``cfg``), all same-shaped pages share
    a single jit trace.
    """
    n = int(data.size)
    return _dispatch("compress", "fz.compress", _compress_with_eb_jit,
                     (data, eb_abs), cfg, n, _source_dtype_name(data), n=n)


def _compress_core(data: jax.Array, eb: jax.Array, cfg: FZConfig,
                   dtype_name: str = "float32") -> FZCompressed:
    if _fused(cfg):
        from repro.kernels import ops as kops
        bitflags, payload, nnz, oidx, oval, n_over = kops.fused_compress_stages(
            data, eb, code_mode=cfg.code_mode,
            capacity=cfg.payload_capacity(data.size),
            outlier_capacity=cfg.outlier_capacity(data.size))
        return FZCompressed(bitflags=bitflags, payload=payload, nnz_blocks=nnz,
                            outlier_idx=oidx, outlier_val=oval,
                            n_outliers=jnp.minimum(n_over, oidx.size).astype(jnp.int32),
                            eb_abs=eb, shape=tuple(data.shape), dtype_name=dtype_name)
    quantize, shuffle_encode, _ = _stages(cfg)
    with obs.span("fz.stage.quantize"):
        codes, oidx, oval, n_over = quantize(
            data, eb, code_mode=cfg.code_mode,
            outlier_capacity=cfg.outlier_capacity(data.size))
        with obs.span("fz.stage.collect_outliers"):
            n_outliers = jnp.minimum(n_over, oidx.size).astype(jnp.int32)
    with obs.span("fz.stage.shuffle_encode"):
        flat = shuffle.pad_to_tiles(codes.reshape(-1))
        bitflags, payload, nnz = shuffle_encode(
            flat, capacity=cfg.payload_capacity(data.size))
    return FZCompressed(bitflags=bitflags, payload=payload, nnz_blocks=nnz,
                        outlier_idx=oidx, outlier_val=oval, n_outliers=n_outliers,
                        eb_abs=eb, shape=tuple(data.shape), dtype_name=dtype_name)


@partial(jax.jit, static_argnames=("cfg",))
def _decompress_jit(c: FZCompressed, cfg: FZConfig) -> jax.Array:
    cfg = _static_auto(cfg, c.n)
    if _fused(cfg):
        from repro.kernels import ops as kops
        return kops.fused_decompress(
            c.bitflags, c.payload, c.eb_abs, shape=c.shape,
            code_mode=cfg.code_mode,
            outlier_idx=c.outlier_idx if cfg.exact_outliers else None,
            outlier_val=c.outlier_val if cfg.exact_outliers else None)
    _, _, unshuffle = _stages(cfg)
    with obs.span("fz.stage.decode_blocks"):
        words = enc.decode_blocks(c.bitflags, c.payload,
                                  n_blocks=FZConfig.n_blocks(c.n))
    with obs.span("fz.stage.unshuffle"):
        codes = unshuffle(words)[: c.n]
    oidx = c.outlier_idx if cfg.exact_outliers else None
    oval = c.outlier_val if cfg.exact_outliers else None
    with obs.span("fz.stage.dequantize"):
        return quant.dual_dequantize(codes, c.eb_abs, c.shape, code_mode=cfg.code_mode,
                                     outlier_idx=oidx, outlier_val=oval)


def decompress(c: FZCompressed, cfg: FZConfig) -> jax.Array:
    """Inverse pipeline: decode -> bit-unshuffle -> inverse Lorenzo -> dequant."""
    return _dispatch("decompress", "fz.decompress", _decompress_jit, (c,), cfg,
                     c.n, c.dtype_name, n=c.n)


def decompress_unmetered(c: FZCompressed, cfg: FZConfig) -> jax.Array:
    """``decompress`` without dispatch counting/spans — for the error-bound
    sentinels' sampled roundtrip checks, which must not perturb the dispatch
    accounting they audit (same compiled program, bit-identical output)."""
    return _decompress_jit(c, _resolved(cfg, "decompress", c.n, c.dtype_name))


def lowered(op: str, arg, cfg: FZConfig) -> jax.stages.Lowered:
    """The program that ``compress(arg, cfg)`` (``op="compress"``) or
    ``decompress(arg, cfg)`` (``op="decompress"``) dispatches, lowered: the
    same jitted inner at the same resolved config. ``arg`` may be a
    ``jax.ShapeDtypeStruct`` or a container of them. Compiling it hits the
    cache of the dispatched program, and its compiled text names the
    instructions a profile of that dispatch shows."""
    if op == "compress":
        return _compress_jit.lower(arg, _resolved(cfg, op, int(arg.size),
                                                  _source_dtype_name(arg)))
    if op == "decompress":
        return _decompress_jit.lower(arg, _resolved(cfg, op, arg.n, arg.dtype_name))
    raise ValueError(f"unknown op {op!r}")


def roundtrip(data: jax.Array, cfg: FZConfig):
    """compress + decompress; returns (reconstruction, container)."""
    c = compress(data, cfg)
    return decompress(c, cfg), c


# ---------------------------------------------------------------------------
# Batched page entry points (one vmapped launch = one counted dispatch)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def _compress_batch_jit(pages_flat, eb_abs, cfg: FZConfig):
    cfg = _static_auto(cfg, pages_flat.size // pages_flat.shape[0])
    return jax.vmap(lambda d: _compress_with_eb_jit(d, eb_abs, cfg))(pages_flat)


def compress_batch_with_eb(pages_flat: jax.Array, eb_abs: jax.Array,
                           cfg: FZConfig) -> FZCompressed:
    """vmap ``compress_with_eb`` over same-shaped rows: one dispatch for the
    whole set. Elementwise math at a shared traced bound — each row is
    bit-identical to a single-row ``compress_with_eb`` call. This is the
    kvpool cold tier's batched park path."""
    return _dispatch("compress", "fz.compress_batch", _compress_batch_jit,
                     (pages_flat, eb_abs), cfg,
                     int(pages_flat.size // pages_flat.shape[0]),
                     _source_dtype_name(pages_flat), rows=int(pages_flat.shape[0]))


@partial(jax.jit, static_argnames=("cfg",))
def _decompress_batch_jit(comp: FZCompressed, cfg: FZConfig):
    cfg = _static_auto(cfg, comp.n)
    return jax.vmap(lambda c: _decompress_jit(c, cfg))(comp)


def decompress_batch(comp: FZCompressed, cfg: FZConfig) -> jax.Array:
    """vmap ``decompress`` over a leaf-stacked container batch (one counted
    dispatch) — the kvpool's batched transient cold read."""
    return _dispatch("decompress", "fz.decompress_batch", _decompress_batch_jit,
                     (comp,), cfg, comp.n, comp.dtype_name,
                     rows=int(comp.payload.shape[0]))


# ---------------------------------------------------------------------------
# Pytree helpers (gradients, optimizer states, checkpoints)
# ---------------------------------------------------------------------------

def tree_compress(tree: Any, cfg: FZConfig) -> Any:
    """Compress every float leaf of a pytree (leaves >= 1 tile; small leaves pass through)."""
    def leaf_fn(x):
        if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating) \
                and x.size >= shuffle.TILE and x.ndim <= 3:
            return compress(x, cfg)
        return x
    return jax.tree.map(leaf_fn, tree)


def tree_decompress(tree: Any, cfg: FZConfig, dtypes: Any | None = None) -> Any:
    def leaf_fn(x):
        return decompress(x, cfg) if isinstance(x, FZCompressed) else x
    out = jax.tree.map(leaf_fn, tree, is_leaf=lambda x: isinstance(x, FZCompressed))
    if dtypes is not None:
        out = jax.tree.map(lambda x, d: x.astype(d), out, dtypes)
    return out


# ---------------------------------------------------------------------------
# Serialized byte containers (cold tier / checkpoints)
#
# The pytree container above is the hot-path wire format: fixed shapes,
# jit/collective-safe, capacity-padded. When a container leaves the compute
# graph — parked KV pages, checkpoint leaves — it is serialized to the exact
# versioned byte stream below, optionally with the second-stage entropy coder
# (core/entropy.py) over the payload bytes. Byte-level spec + version
# history: docs/CONTAINER_FORMAT.md. Everything here is host-side numpy and
# must never be called from inside a trace.
# ---------------------------------------------------------------------------

CONTAINER_MAGIC = b"FZGC"
CONTAINER_VERSION = 1

FLAG_ENTROPY = 1 << 0    # payload section is a core.entropy blob
FLAG_ZIGZAG = 1 << 1     # zigzag quantization codes (else sign-magnitude)
FLAG_OUTLIERS = 1 << 2   # exact-outlier channel present in the stream

ENTROPY_MIN_GAIN = 0.02      # probe must predict >= 2% saving to encode
_MIN_ENTROPY_BYTES = 256     # below this the blob overhead can't win

_DTYPE_CODES = {"float32": 0, "bfloat16": 1, "float16": 2, "float64": 3}
_DTYPE_NAMES = {v: k for k, v in _DTYPE_CODES.items()}

_HDR = struct.Struct("<4sHHBBH")   # magic, version, flags, ndim, dtype, rsvd
_TAIL = struct.Struct("<QQfQ")     # nnz, n_outliers, eb_abs, payload_len
_LEGACY_HDR_BYTES = 28             # i64 n/nnz/n_out + f32 eb (pre-v1 streams)


class FZFormatError(ValueError):
    """Raised for malformed, truncated, or unsupported serialized containers."""


def to_bytes(c: FZCompressed, cfg: FZConfig, *, entropy: bool | str = "auto",
             chunk_bytes: int = ent.DEFAULT_CHUNK,
             tier: str | None = None) -> bytes:
    """Serialize a container to the exact v1 byte stream.

    ``entropy``: ``"auto"`` probes the payload byte histogram
    (`core.entropy.plan`) and entropy-codes only when the *exact* predicted
    blob is >= ``ENTROPY_MIN_GAIN`` smaller; ``True``/``False`` force the
    choice. The selection is recorded in the header flags so
    :func:`from_bytes` routes transparently. ``tier`` labels the
    ``entropy_stage`` counters. Ratio-EWMA feeding (`obs.note_ratio`) is
    deliberately left to callers — they know their sampling discipline; a
    per-call EWMA here would let ordinary page-to-page variance trip the
    ratio-drift sentinel.
    """
    if entropy not in (True, False, "auto"):
        raise ValueError(f"entropy must be True/False/'auto', got {entropy!r}")
    if c.dtype_name not in _DTYPE_CODES:
        raise FZFormatError(f"unserializable container dtype {c.dtype_name!r}")
    nnz = int(c.nnz_blocks)
    rows = min(nnz, int(c.payload.shape[1]))
    n_out = int(c.n_outliers)
    payload = np.asarray(c.payload)[:, :rows].T.astype("<u2").tobytes()

    selected = False
    body = payload
    if entropy is True or (entropy == "auto"
                           and len(payload) >= _MIN_ENTROPY_BYTES):
        counts = np.bincount(np.frombuffer(payload, np.uint8), minlength=256)
        lengths, est = ent.plan(counts, len(payload), chunk_bytes)
        if entropy is True or est <= len(payload) * (1.0 - ENTROPY_MIN_GAIN):
            blob = ent.encode(payload, chunk_bytes, lengths=lengths)
            if entropy is True or len(blob) < len(payload):
                selected, body = True, blob
    obs.counter("entropy_stage", op="encode",
                selected=str(selected).lower(), tier=tier or "adhoc").inc()

    flags = ((FLAG_ENTROPY if selected else 0)
             | (FLAG_ZIGZAG if cfg.code_mode == "zigzag" else 0)
             | (FLAG_OUTLIERS if cfg.exact_outliers else 0))
    parts = [
        _HDR.pack(CONTAINER_MAGIC, CONTAINER_VERSION, flags, len(c.shape),
                  _DTYPE_CODES[c.dtype_name], 0),
        np.asarray(c.shape, "<u8").tobytes(),
        _TAIL.pack(nnz, n_out, float(c.eb_abs), len(body)),
        np.asarray(c.bitflags).astype("<u4").tobytes(),
        body,
    ]
    if flags & FLAG_OUTLIERS:
        parts.append(np.asarray(c.outlier_idx)[:n_out].astype("<i4").tobytes())
        parts.append(np.asarray(c.outlier_val)[:n_out].astype("<i4").tobytes())
    return b"".join(parts)


def _np_slice(raw: memoryview, dtype: str, count: int, offset: int,
              what: str) -> np.ndarray:
    itemsize = np.dtype(dtype).itemsize
    if offset + count * itemsize > len(raw):
        raise FZFormatError(f"container truncated in {what} section "
                            f"({len(raw)} bytes)")
    return np.frombuffer(raw, dtype, count, offset)


def from_bytes(raw: bytes, *, capacity: int | None = None,
               outlier_capacity: int | None = None,
               tier: str | None = None) -> tuple[FZCompressed, FZConfig]:
    """Parse a serialized container back into the fixed-shape pytree form.

    Reconstruction is *bit-exact*: payload rows past ``nnz`` are zero,
    outlier index slots past ``n_outliers`` hold ``n`` and value slots 0 —
    the same fill conventions ``compress`` produces — so a deserialized
    container is leaf-identical to the one serialized (at equal capacities)
    and safe to stack into vmapped batch decodes. ``capacity`` /
    ``outlier_capacity`` override the padded sizes (the kvpool passes its
    pool-wide capacities so blob-backed pages stack with slot-backed ones);
    defaults are the tightest sizes that decode exactly.

    Streams without the ``FZGC`` magic are parsed as the legacy headerless
    checkpoint stream written before the format was versioned; a version
    newer than ``CONTAINER_VERSION`` raises :class:`FZFormatError`.

    Returns ``(container, cfg)`` where ``cfg`` carries the decode-relevant
    statics (code_mode, exact_outliers) — its ``eb`` field is fixed at 0.0
    (the real bound travels in ``container.eb_abs``; keeping ``cfg`` constant
    avoids a retrace per distinct bound).
    """
    raw = memoryview(raw)
    if bytes(raw[:4]) != CONTAINER_MAGIC:
        return _from_legacy_bytes(raw, capacity=capacity,
                                  outlier_capacity=outlier_capacity, tier=tier)
    if len(raw) < _HDR.size + _TAIL.size:
        raise FZFormatError(f"container truncated: {len(raw)} bytes")
    _, version, flags, ndim, dtcode, _ = _HDR.unpack_from(raw, 0)
    if version != CONTAINER_VERSION:
        raise FZFormatError(
            f"FZ container version {version} is not supported by this build "
            f"(max {CONTAINER_VERSION}); upgrade repro or re-serialize with "
            f"a matching version")
    if dtcode not in _DTYPE_NAMES:
        raise FZFormatError(f"unknown container dtype code {dtcode}")
    off = _HDR.size
    shape = tuple(int(v) for v in _np_slice(raw, "<u8", ndim, off, "shape"))
    off += 8 * ndim
    nnz, n_out, eb_abs, payload_len = _TAIL.unpack_from(raw, off)
    off += _TAIL.size
    n = 1
    for s in shape:
        n *= s
    fw = enc.flag_words(FZConfig.n_blocks(n))
    bitflags = _np_slice(raw, "<u4", fw, off, "bitflags").copy()
    off += 4 * fw
    if off + payload_len > len(raw):
        raise FZFormatError(f"container truncated in payload section "
                            f"({len(raw)} bytes)")
    body = bytes(raw[off:off + payload_len])
    off += payload_len
    if flags & FLAG_ENTROPY:
        body = ent.decode(body)
    obs.counter("entropy_stage", op="decode",
                selected=str(bool(flags & FLAG_ENTROPY)).lower(),
                tier=tier or "adhoc").inc()

    rows = len(body) // enc.BLOCK_BYTES
    cap = max(rows, 1) if capacity is None else capacity
    if cap < rows:
        raise FZFormatError(f"capacity {cap} < {rows} stored payload rows")
    payload = np.zeros((enc.BLOCK_WORDS, cap), np.uint16)
    payload[:, :rows] = np.frombuffer(body, "<u2").reshape(rows, enc.BLOCK_WORDS).T

    if flags & FLAG_OUTLIERS:
        oidx = _np_slice(raw, "<i4", n_out, off, "outlier idx")
        off += 4 * n_out
        oval = _np_slice(raw, "<i4", n_out, off, "outlier val")
        ocap = max(n_out, 1) if outlier_capacity is None else outlier_capacity
        if ocap < n_out:
            raise FZFormatError(f"outlier_capacity {ocap} < {n_out} stored")
    else:
        oidx = oval = np.zeros(0, np.int32)
        ocap = outlier_capacity or 0
    oi = np.full((ocap,), n, np.int32)
    oi[:n_out if flags & FLAG_OUTLIERS else 0] = oidx
    ov = np.zeros((ocap,), np.int32)
    ov[:n_out if flags & FLAG_OUTLIERS else 0] = oval

    c = FZCompressed(
        bitflags=jnp.asarray(bitflags), payload=jnp.asarray(payload),
        nnz_blocks=jnp.int32(nnz), outlier_idx=jnp.asarray(oi),
        outlier_val=jnp.asarray(ov), n_outliers=jnp.int32(n_out),
        eb_abs=jnp.float32(eb_abs), shape=shape,
        dtype_name=_DTYPE_NAMES[dtcode])
    cfg = FZConfig(eb=0.0, eb_mode="abs",
                   code_mode="zigzag" if flags & FLAG_ZIGZAG else "sign_mag",
                   exact_outliers=bool(flags & FLAG_OUTLIERS),
                   use_kernels=False)
    return c, cfg


def _from_legacy_bytes(raw: memoryview, *, capacity: int | None,
                       outlier_capacity: int | None,
                       tier: str | None) -> tuple[FZCompressed, FZConfig]:
    """Parse the headerless pre-v1 checkpoint stream (ckpt/checkpoint.py
    before the container format was versioned): i64 [n, nnz, n_outliers],
    f32 eb_abs, u32 bitflags, u16 payload rows, i32 outlier idx + val."""
    if len(raw) < _LEGACY_HDR_BYTES:
        raise FZFormatError(f"not an FZ container: {len(raw)} bytes, no magic")
    n, nnz, n_out = (int(v) for v in np.frombuffer(raw, "<i8", 3, 0))
    eb_abs = float(np.frombuffer(raw, "<f4", 1, 24)[0])
    if n <= 0 or nnz < 0 or n_out < 0:
        raise FZFormatError("not an FZ container: no magic and implausible "
                            "legacy header")
    fw = enc.flag_words(FZConfig.n_blocks(n))
    expect = _LEGACY_HDR_BYTES + 4 * fw + enc.BLOCK_BYTES * nnz + 8 * n_out
    if len(raw) != expect:
        raise FZFormatError(
            f"not an FZ container: no magic and legacy stream length "
            f"mismatch ({len(raw)} bytes, expected {expect})")
    off = _LEGACY_HDR_BYTES
    bitflags = np.frombuffer(raw, "<u4", fw, off).copy()
    off += 4 * fw
    rows = np.frombuffer(raw, "<u2", enc.BLOCK_WORDS * nnz, off
                         ).reshape(nnz, enc.BLOCK_WORDS)
    off += enc.BLOCK_BYTES * nnz
    oidx = np.frombuffer(raw, "<i4", n_out, off)
    off += 4 * n_out
    oval = np.frombuffer(raw, "<i4", n_out, off)

    cap = max(nnz, 1) if capacity is None else capacity
    payload = np.zeros((enc.BLOCK_WORDS, cap), np.uint16)
    payload[:, :nnz] = rows.T
    ocap = max(n_out, 1) if outlier_capacity is None else outlier_capacity
    oi = np.full((ocap,), n, np.int32)
    oi[:n_out] = oidx
    ov = np.zeros((ocap,), np.int32)
    ov[:n_out] = oval
    obs.counter("entropy_stage", op="decode", selected="false",
                tier=tier or "adhoc").inc()
    c = FZCompressed(
        bitflags=jnp.asarray(bitflags), payload=jnp.asarray(payload),
        nnz_blocks=jnp.int32(nnz), outlier_idx=jnp.asarray(oi),
        outlier_val=jnp.asarray(ov), n_outliers=jnp.int32(n_out),
        eb_abs=jnp.float32(eb_abs), shape=(n,), dtype_name="float32")
    return c, FZConfig(eb=0.0, eb_mode="abs", exact_outliers=True,
                       use_kernels=False)


def decompress_bytes(raw: bytes, *, tier: str | None = None) -> jax.Array:
    """One-call reconstruction from a serialized container (any supported
    version): parse, entropy-decode if flagged, run the jitted inverse
    pipeline. The decode routes transparently — callers never inspect the
    entropy flag themselves."""
    c, cfg = from_bytes(raw, tier=tier)
    return decompress(c, cfg)
