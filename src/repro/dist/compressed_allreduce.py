"""FZ-compressed cross-pod gradient mean with error feedback (§2.4 "wire").

Gradients crossing the slow inter-pod (DCN) link are the framework's most
movement-bound tensor stream, so they get the paper's wire-compression
treatment: each pod compresses its local gradient (plus the carried
error-feedback residual) into the fixed-shape FZ container, the containers —
not the raw f32 tensors — cross the pod boundary, every pod decompresses all
containers locally, and the reduced gradient is the exact mean of the
reconstructions. The per-pod quantization error is stored back into the
error state and replayed into the next round's input, so the *time-averaged*
reduced gradient converges to the exact mean (standard error-feedback
compression; verified in tests/test_dist.py).

Execution model: hybrid — the loss/backward stays fully XLA-automatic (the
step builder vmaps it over a stacked leading pod dimension), and only the
reduce hops run as manual shard_map regions over the ``pod`` axis:
compress locally, ``all_gather`` the container leaves over ``pod``,
decompress all pods on every device, mean. The reduce comes in two issue
granularities sharing this per-leaf math bit-for-bit:

  * this module's ``reduce_stacked`` — ONE region per leaf, all issued
    after the full backward pass (a barrier at the end of the step). It is
    the parity ORACLE: simple, and bit-identical to the bucketed path.
  * ``bucketed_reduce.reduce_stacked_bucketed`` — leaves grouped into
    size-targeted buckets, one region per bucket issued in backward
    production order, so each bucket's DCN transfer can overlap the
    remaining backward compute (``train/step.py`` overlap path,
    ``launch/train.py --overlap-reduce``).

Two reasons the hop is manual:
(1) the wire format is structural — the only tensors that can cross the
pod boundary are the capacity-sized container buffers, independent of any
partitioner choice; (2) the FZ pipeline (integer prefix sums, bit packing,
gather compaction) must not be sliced by the SPMD partitioner at all —
under sharding pressure from the optimizer's param-sharded outputs the
partitioner is free to split the scan axis of ``cumsum``/gather chains,
which (observed on the pinned XLA CPU backend) silently corrupts the
decoded stream. Inside shard_map each device runs the whole per-pod
pipeline redundantly on its replica — compression math is elementwise/
O(n log n), cheap next to the backward pass that produced the gradient.

Wire accounting (``wire_bytes_per_leaf``) is shape-static by construction:
the container's leaves are capacity-sized, so bytes-on-the-wire depend only
on the element count and the config, never on the data. It agrees exactly
with ``FZCompressed.wire_bytes()`` and upper-bounds ``used_bytes()``
(tests/test_wire_accounting.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fz


@dataclasses.dataclass(frozen=True)
class GradCompressionConfig:
    """Static configuration for the compressed cross-pod reduce."""
    enabled: bool = False
    eb: float = 1e-4               # error bound on each pod's gradient
    eb_mode: str = "rel"           # relative to the leaf's value range
    code_mode: str = "sign_mag"
    capacity_frac: float = 1.0     # container payload capacity vs worst case
    min_leaf_size: int = 4096      # elements; smaller leaves reduce exactly
    # bucketed/overlapped issue (dist/bucketed_reduce.py): when ``overlap``
    # is on, the step builder routes the reduce through per-bucket hops
    # (``bucket_bytes`` of wire traffic each) interleaved with the backward
    # pass; off keeps the legacy single-barrier reduce below.
    overlap: bool = False
    bucket_bytes: int = 4 << 20
    # per-leaf FZ hops through the Pallas kernels ("fused" = single-launch
    # megakernels, "staged" = per-stage oracle); off keeps the jnp reference.
    # Both reduces (barrier reduce_stacked and the bucketed hops) share this
    # config, so the bit-parity oracle relationship between them holds under
    # every kernel flavor.
    use_kernels: bool = False
    kernel_mode: str = "auto"

    def fz_config(self) -> fz.FZConfig:
        # exact_outliers off: saturation error (like dropped blocks when
        # capacity_frac < 1) is absorbed by the error-feedback residual.
        return fz.FZConfig(eb=self.eb, eb_mode=self.eb_mode,
                           code_mode=self.code_mode,
                           capacity_frac=self.capacity_frac,
                           exact_outliers=False,
                           use_kernels=self.use_kernels,
                           kernel_mode=self.kernel_mode)


def _compressible(shape: tuple[int, ...], dtype, cfg: GradCompressionConfig) -> bool:
    n = 1
    for s in shape:
        n *= s
    return bool(jnp.issubdtype(dtype, jnp.floating)) and n >= cfg.min_leaf_size


def wire_bytes_per_leaf(n_elems: int, cfg: GradCompressionConfig) -> dict:
    """Bytes a single f32 leaf of ``n_elems`` puts on the cross-pod link.

    Derived from the abstract container itself (``eval_shape`` of
    ``fz.compress``), so it equals ``FZCompressed.wire_bytes()`` by
    construction: the container's leaves are capacity-sized, making the
    wire cost a pure function of element count and config.
    """
    fzc = cfg.fz_config()
    raw = 4 * n_elems
    c_abs = jax.eval_shape(lambda x: fz.compress(x, fzc),
                           jax.ShapeDtypeStruct((n_elems,), jnp.float32))
    compressed = sum(int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
                     for leaf in jax.tree.leaves(c_abs))
    return {"raw": raw, "compressed": compressed, "reduction": raw / compressed}


def init_error_state(grads_abstract: Any, n_pods: int,
                     cfg: GradCompressionConfig) -> Any:
    """Zero error-feedback residuals, stacked over the leading pod dim.

    Bypass leaves (small / non-float: reduced exactly) carry an empty f32
    placeholder so the error state mirrors the gradient structure without
    spending memory on leaves that never accumulate error.
    """
    if not cfg.enabled:
        return {}

    def one(ab):
        if _compressible(tuple(ab.shape), ab.dtype, cfg):
            return jnp.zeros((n_pods,) + tuple(ab.shape), jnp.float32)
        return jnp.zeros((0,), jnp.float32)
    return jax.tree.map(one, grads_abstract)


def error_state_shardings(grads_abstract: Any, cfg: GradCompressionConfig,
                          mesh) -> Any:
    """Shardings for the error state: stacked pod dim on the pod axis."""
    if not cfg.enabled:
        return {}
    has_pod = "pod" in tuple(mesh.axis_names)

    def one(ab):
        if _compressible(tuple(ab.shape), ab.dtype, cfg) and has_pod:
            return NamedSharding(mesh, P("pod"))
        return NamedSharding(mesh, P())
    return jax.tree.map(one, grads_abstract)


def _roundtrip_per_pod(x: jax.Array, fzc: fz.FZConfig) -> jax.Array:
    """(n_pods, n) -> per-pod compress+decompress reconstruction, stacked.

    Python loop over the (static, small) pod count; the no-mesh reference
    path for tests and single-device numerics.
    """
    d = [fz.decompress(fz.compress(x[p], fzc), fzc) for p in range(x.shape[0])]
    return jnp.stack(d)


def reference_hop(x: jax.Array, fzc: fz.FZConfig) -> tuple[jax.Array, jax.Array]:
    """No-mesh reduce hop: (n_pods, n) -> (mean (n,), residual (n_pods, n))."""
    d = _roundtrip_per_pod(x, fzc)
    return jnp.mean(d, axis=0), x - d


def pod_hop_body(xi: jax.Array, fzc: fz.FZConfig) -> tuple[jax.Array, jax.Array]:
    """One leaf's wire hop, to be called INSIDE a shard_map over ``pod``.

    ``xi``: this pod's (n,) f32 slice (gradient + replayed residual).
    Compress locally, ``all_gather`` the container leaves over ``pod`` (the
    only tensors that cross the pod boundary), decompress every pod's
    container, mean; the residual is against this pod's own reconstruction.
    Shared by the barrier reduce below and the bucketed reduce
    (dist/bucketed_reduce.py) — their bit parity is by construction because
    this is the single definition of the per-leaf math.
    """
    c = fz.compress(xi, fzc)
    c_all = jax.tree.map(lambda leaf: jax.lax.all_gather(leaf, "pod"), c)
    me = jax.lax.axis_index("pod")

    # one pod's container at a time: a vmap would hold every pod's (n,)
    # reconstruction at once, which at a 64000x4096 embedding leaf is what
    # overflows a 16 GB chip
    def add_pod(carry, ci_i):
        total, mine = carry
        ci, i = ci_i
        d = fz.decompress(ci, fzc)
        return (total + d, jnp.where(i == me, d, mine)), None

    zero = jnp.zeros(xi.shape, jnp.float32)
    n_pods = c_all.payload.shape[0]
    (total, mine), _ = jax.lax.scan(
        add_pod, (zero, zero), (c_all, jnp.arange(n_pods)))
    return total / n_pods, (xi - mine)[None]


def reduce_stacked(g_stack: Any, err_state: Any, cfg: GradCompressionConfig,
                   mesh=None) -> tuple[Any, Any]:
    """Compressed mean over a stacked leading pod dimension.

    ``g_stack`` leaves are ``(n_pods, *leaf_shape)``; returns the reduced
    ``(*leaf_shape)`` tree plus the updated error state. Leaves below
    ``min_leaf_size`` (and non-float leaves) are reduced exactly and their
    error placeholder passes through untouched.

    With a multi-pod ``mesh`` the reduce hop runs as a manual shard_map
    over ``pod`` (see module docstring); without one (single-device tests,
    reference numerics) the identical math runs inline.
    """
    if not cfg.enabled:
        red = jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), axis=0)
                           .astype(g.dtype), g_stack)
        return red, err_state

    fzc = cfg.fz_config()
    has_pod = mesh is not None and "pod" in tuple(mesh.axis_names)

    def sharded_roundtrip(x):
        """x: (n_pods, n) -> (mean (n,), residual (n_pods, n)) via shard_map."""
        def body(x_sh):
            return pod_hop_body(x_sh[0], fzc)   # x_sh[0]: this pod's slice

        # fully manual (axis_names=None): data/model must also be manual so
        # the partitioner can never slice the FZ pipeline's scan axis — the
        # body is replicated across them (in/out specs only use "pod")
        return jax.shard_map(
            body, mesh=mesh, in_specs=(P("pod"),),
            out_specs=(P(), P("pod")), check_vma=False)(x)

    def one(g, e):
        n_pods = g.shape[0]
        leaf_shape = g.shape[1:]
        if not _compressible(leaf_shape, g.dtype, cfg):
            return (jnp.mean(g.astype(jnp.float32), axis=0).astype(g.dtype), e)
        x = g.astype(jnp.float32).reshape(n_pods, -1) + e.reshape(n_pods, -1)
        if has_pod:
            red, new_e = sharded_roundtrip(x)
        else:
            red, new_e = reference_hop(x, fzc)
        return (red.reshape(leaf_shape).astype(g.dtype),
                new_e.reshape((n_pods,) + leaf_shape))

    pairs = jax.tree.map(one, g_stack, err_state)
    # explicit outer treedef: safe even when g_stack itself contains tuples
    red, new_err = jax.tree.transpose(
        jax.tree.structure(g_stack), jax.tree.structure((0, 0)), pairs)
    return red, new_err
