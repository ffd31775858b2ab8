"""Bucketed, overlap-ready FZ-compressed cross-pod gradient reduce.

The barrier reduce in ``compressed_allreduce`` compresses every leaf inside
one region issued after the whole backward pass — the DCN transfers cannot
start until the last gradient exists, so the wire time adds to the step
instead of hiding inside it (the paper's §2.4 argument is exactly that
compression only pays when it hides inside the movement it saves). This
module restructures the same math into independently schedulable pieces:

  * Per-leaf FZ hops inherit ``GradCompressionConfig.use_kernels`` /
    ``kernel_mode`` through the shared ``fz_config()``: with kernels on,
    every bucket hop's compress and decompress run as the single-launch
    megakernels (kernels/fused_compress, kernels/fused_decode) inside the
    shard_map region. Compression stays strictly per leaf, so the barrier
    ``reduce_stacked`` remains a bit-parity oracle under every kernel
    flavor — the fused/staged/reference paths produce identical containers.
  * ``assign_buckets`` partitions the gradient pytree into size-targeted
    buckets (``GradCompressionConfig.bucket_bytes`` of *wire* bytes each).
    The assignment is a pure function of the abstract gradient tree and the
    config — deterministic and stable across steps — so the error-feedback
    residuals stay aligned with their leaves for the whole run.
  * Leaves are ordered by backward *production* order (unembed first,
    final norm, then the scanned layer stack, embedding last) and buckets
    are contiguous ranges of that order, so the first hops issued are the
    ones whose inputs exist first.
  * ``reduce_stacked_bucketed`` issues one manual ``shard_map`` region per
    bucket (compress -> ``all_gather("pod")`` -> decompress -> mean, with
    error feedback), in production order. Each region depends only on its
    own leaves' cotangents, so XLA's latency-hiding scheduler (flags
    promoted into ``launch/train.py --overlap-reduce``) can run a bucket's
    DCN transfer while the remaining backward compute is still producing
    later buckets.
  * ``grad_boundary`` is a ``custom_vjp`` identity installed on the model's
    parameter-group boundaries (``models/transformer.py`` via
    ``nn.grad_tap``). Its backward applies an ``optimization_barrier`` to
    the cotangents, pinning each group's gradients as a distinct scheduling
    unit instead of letting XLA fuse them into later backward clusters —
    the point where a bucket's input is "ready" is then a real boundary in
    the schedule.

Compression stays strictly per leaf inside a bucket (each leaf keeps its
own relative error bound, container, and residual), so the arithmetic is
*identical* to the barrier path: same buckets or not, the reduced gradients
and the error state are bit-identical to ``reduce_stacked`` — the barrier
reduce is retained as the parity oracle (tests/test_dist.py,
tests/test_bucketed_reduce.py).

Wire accounting: every bucket hop all-gathers its leaves' container
buffers, so per-bucket cross-pod bytes are analytic. ``launch/hlo_cost``
attributes cross-pod collectives to buckets via the ``bucket<i>_reduce``
named-scope tag that wraps each hop; ``expected_cross_pod_bytes`` is the
model it must match (the compiled HLO drops the container's ``nnz_blocks``
/ ``n_outliers`` bookkeeping scalars, which the mean hop never reads —
``gathered_bytes_per_leaf`` accounts for exactly the leaves that survive).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import fz
from .compressed_allreduce import (GradCompressionConfig, _compressible,
                                   pod_hop_body, reference_hop,
                                   wire_bytes_per_leaf)

# Backward production order of the transformer's top-level parameter groups
# (models/transformer.py): the unembed cotangent exists first (closest to
# the loss), the scanned layer stack finishes next-to-last, the embedding
# gather's backward runs last. Unknown groups (other model families) slot in
# with the layer stack; ties break on the leaf path, so the order is total
# and deterministic for any tree.
_PRODUCTION_RANK = {"unembed": 0, "final_norm": 1, "layers": 2, "embed": 4}
_DEFAULT_RANK = 2


def _top_level_name(path) -> str:
    for entry in path:
        key = getattr(entry, "key", None)
        if key is not None:
            return str(key)
        name = getattr(entry, "name", None)
        if name is not None:
            return str(name)
    return ""


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One reduce hop: a contiguous production-order run of leaves."""
    index: int
    keys: tuple[str, ...]          # leaf paths (jax.tree_util.keystr form)
    n_elems: tuple[int, ...]       # flattened element count per leaf
    wire_bytes: int                # one pod's compressed bytes on the link

    @property
    def tag(self) -> str:
        """Named-scope tag wrapping this bucket's hop (hlo_cost attribution)."""
        return f"bucket{self.index}_reduce"


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    buckets: tuple[Bucket, ...]
    bypass: tuple[str, ...]        # small/non-float leaves: reduced exactly

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)


def assign_buckets(grads_abstract: Any, cfg: GradCompressionConfig) -> BucketPlan:
    """Deterministic leaf -> bucket assignment from the abstract grad tree.

    Pure in (abstract shapes/dtypes, config): rebuilding the plan on any
    step, host, or process yields the same buckets, which is what keeps the
    error-feedback state aligned with its leaves across restarts. Leaves are
    greedily packed in production order until the next leaf would push the
    bucket past ``cfg.bucket_bytes`` of wire traffic; a single leaf larger
    than the target gets its own bucket.
    """
    leaves = jax.tree_util.tree_flatten_with_path(grads_abstract)[0]
    ordered, bypass = [], []
    wire_cache: dict[int, int] = {}
    for path, ab in leaves:
        key = jax.tree_util.keystr(path)
        shape, dtype = tuple(ab.shape), ab.dtype
        if not _compressible(shape, dtype, cfg):
            bypass.append(key)
            continue
        n = 1
        for s in shape:
            n *= s
        if n not in wire_cache:
            wire_cache[n] = int(wire_bytes_per_leaf(n, cfg)["compressed"])
        rank = _PRODUCTION_RANK.get(_top_level_name(path), _DEFAULT_RANK)
        ordered.append((rank, key, n, wire_cache[n]))
    ordered.sort(key=lambda t: (t[0], t[1]))

    buckets: list[Bucket] = []
    cur_keys: list[str] = []
    cur_ns: list[int] = []
    cur_bytes = 0

    def flush():
        nonlocal cur_keys, cur_ns, cur_bytes
        if cur_keys:
            buckets.append(Bucket(index=len(buckets), keys=tuple(cur_keys),
                                  n_elems=tuple(cur_ns), wire_bytes=cur_bytes))
            cur_keys, cur_ns, cur_bytes = [], [], 0

    for _, key, n, wb in ordered:
        if cur_keys and cur_bytes + wb > cfg.bucket_bytes:
            flush()
        cur_keys.append(key)
        cur_ns.append(n)
        cur_bytes += wb
    flush()
    plan = BucketPlan(buckets=tuple(buckets), bypass=tuple(sorted(bypass)))
    # analytic wire bytes are known at plan time (the hop itself runs inside
    # jit, where nothing may be recorded) — publish them as per-bucket gauges
    # so step_report can join bytes onto the bucket spans without an HLO pass
    if jax.core.trace_ctx.is_top_level():
        obs.gauge("dist_n_buckets").set(plan.n_buckets)
        for b in plan.buckets:
            obs.gauge("dist_bucket_wire_bytes", bucket=b.tag).set(b.wire_bytes)
    return plan


# ---------------------------------------------------------------------------
# Wire accounting: what each bucket's hop puts on the cross-pod link
# ---------------------------------------------------------------------------

def gathered_bytes_per_leaf(n_elems: int, cfg: GradCompressionConfig) -> int:
    """Bytes of one leaf's container that actually cross the pod boundary.

    The hop all-gathers the whole container pytree, but the decompress-mean
    consumer only reads ``bitflags``, ``payload`` and ``eb_abs`` (plus the
    outlier leaves in ``exact_outliers`` mode), so XLA dead-code-eliminates
    the gathers of the ``nnz_blocks`` / ``n_outliers`` bookkeeping scalars.
    This is the byte model the compiled HLO matches exactly; it differs from
    ``wire_bytes_per_leaf`` only by those scalars (8 bytes at the gradient
    config), which a real serialized wire format would still carry.
    """
    fzc = cfg.fz_config()
    c = jax.eval_shape(lambda x: fz.compress(x, fzc),
                       jax.ShapeDtypeStruct((n_elems,), jnp.float32))
    fields = ["bitflags", "payload", "eb_abs"]
    if fzc.exact_outliers:
        fields += ["outlier_idx", "outlier_val", "n_outliers"]
    return sum(int(getattr(c, f).size) * jnp.dtype(getattr(c, f).dtype).itemsize
               for f in fields)


def expected_cross_pod_bytes(plan: BucketPlan, cfg: GradCompressionConfig,
                             n_pods: int) -> dict[str, int]:
    """Per-bucket all-gather bytes the compiled HLO must show cross-pod.

    Ring model (launch/hlo_cost): an all-gather costs its *output* bytes, so
    each leaf's container contributes ``n_pods *`` its gathered bytes. Keyed
    by the bucket's named-scope tag, matching ``hlo_cost.analyze``'s
    ``cross_pod_by_tag`` with ``tag_pattern=BUCKET_TAG_PATTERN``.
    """
    out = {}
    for b in plan.buckets:
        out[b.tag] = n_pods * sum(gathered_bytes_per_leaf(n, cfg)
                                  for n in b.n_elems)
    return out


BUCKET_TAG_PATTERN = r"(bucket\d+_reduce)"


# ---------------------------------------------------------------------------
# Gradient-boundary taps (installed via models/nn.set_grad_tap)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _boundary(tree):
    return tree


def _boundary_fwd(tree):
    return tree, None


def _boundary_bwd(_, ct):
    return (jax.lax.optimization_barrier(ct),)


_boundary.defvjp(_boundary_fwd, _boundary_bwd)


def grad_boundary(tree: Any, name: str = "") -> Any:
    """custom_vjp identity marking a parameter-group gradient boundary.

    Forward is the identity (bit-exact, so enabling overlap cannot change
    the loss). Backward routes the cotangents through an
    ``optimization_barrier``: the group's gradients become one schedulable
    unit finalized at the boundary, instead of being fused into whatever
    backward cluster XLA builds next — which is what lets the per-bucket
    hops (and their DCN all-gathers) start as soon as their inputs exist.
    """
    with jax.named_scope(f"grad_boundary_{name}" if name else "grad_boundary"):
        return _boundary(tree)


# ---------------------------------------------------------------------------
# The bucketed reduce
# ---------------------------------------------------------------------------

def _bucket_hop(xs: list[jax.Array], fzc: fz.FZConfig, mesh, tag: str):
    """One bucket's wire hop: per-leaf compress -> all_gather -> mean.

    ``xs``: the bucket's leaves as ``(n_pods, n)`` f32 arrays (gradient plus
    replayed residual). Returns (means, residuals) lists. Each leaf runs the
    shared ``compressed_allreduce.pod_hop_body`` — one shard_map region per
    *bucket* instead of per leaf is the only difference from the barrier
    oracle, so the parity is bit-exact by construction. Fully manual over
    every mesh axis for the same partitioner-safety reasons (see that
    module's docstring).
    """
    def body(*xs_sh):
        outs = [pod_hop_body(x_sh[0], fzc) for x_sh in xs_sh]
        return tuple(r for r, _ in outs), tuple(e for _, e in outs)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(P("pod") for _ in xs),
        out_specs=(tuple(P() for _ in xs), tuple(P("pod") for _ in xs)),
        check_vma=False)
    # the span installs a named scope containing the bucket tag — that is
    # what hlo_cost's tag_pattern keys cross-pod bytes on (and what lets
    # step_report join dist_bucket_wire_bytes onto this span's timing); the
    # hop runs under jit, so the span itself is a trace-time no-op
    with obs.span(f"dist.{tag}", leaves=len(xs)):
        reds, resids = fn(*xs)
    return list(reds), list(resids)


def reduce_stacked_bucketed(g_stack: Any, err_state: Any,
                            cfg: GradCompressionConfig, mesh=None,
                            plan: BucketPlan | None = None) -> tuple[Any, Any]:
    """Bucketed compressed mean over a stacked leading pod dimension.

    Drop-in for ``compressed_allreduce.reduce_stacked`` — same signature
    plus an optional precomputed ``plan`` (the step builder computes it once
    from the abstract gradients; passing None rebuilds it, which is cheap
    and deterministic). Bit-identical outputs to the barrier oracle: per
    leaf the math is unchanged, only the issue granularity differs.
    """
    if not cfg.enabled:
        red = jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), axis=0)
                           .astype(g.dtype), g_stack)
        return red, err_state

    fzc = cfg.fz_config()
    has_pod = mesh is not None and "pod" in tuple(mesh.axis_names)
    if plan is None:
        abstract = jax.tree.map(
            lambda g: jax.ShapeDtypeStruct(tuple(g.shape[1:]), g.dtype), g_stack)
        plan = assign_buckets(abstract, cfg)

    g_leaves, g_treedef = jax.tree_util.tree_flatten_with_path(g_stack)
    e_leaves, e_treedef = jax.tree_util.tree_flatten_with_path(err_state)
    g_map = {jax.tree_util.keystr(p): v for p, v in g_leaves}
    e_map = {jax.tree_util.keystr(p): v for p, v in e_leaves}

    red_map: dict[str, jax.Array] = {}
    new_e_map: dict[str, jax.Array] = {}
    for key in plan.bypass:
        g = g_map[key]
        red_map[key] = jnp.mean(g.astype(jnp.float32), axis=0).astype(g.dtype)
        new_e_map[key] = e_map[key]          # empty placeholder, untouched

    # issue hops in production order: bucket 0's all-gathers are the first
    # in the instruction stream, free to overlap the rest of the backward
    for bucket in plan.buckets:
        xs, leaf_shapes, leaf_dtypes = [], [], []
        for key in bucket.keys:
            g, e = g_map[key], e_map[key]
            n_pods = g.shape[0]
            leaf_shapes.append(g.shape[1:])
            leaf_dtypes.append(g.dtype)
            xs.append(g.astype(jnp.float32).reshape(n_pods, -1)
                      + e.reshape(n_pods, -1))
        if has_pod:
            reds, resids = _bucket_hop(xs, fzc, mesh, bucket.tag)
        else:   # reference numerics: the shared no-mesh hop per leaf
            outs = [reference_hop(x, fzc) for x in xs]
            reds = [r for r, _ in outs]
            resids = [e for _, e in outs]
        for key, red, res, shp, dt in zip(bucket.keys, reds, resids,
                                          leaf_shapes, leaf_dtypes):
            red_map[key] = red.reshape(shp).astype(dt)
            new_e_map[key] = res.reshape((res.shape[0],) + tuple(shp))

    red = jax.tree_util.tree_unflatten(
        g_treedef, [red_map[jax.tree_util.keystr(p)] for p, _ in g_leaves])
    new_err = jax.tree_util.tree_unflatten(
        e_treedef, [new_e_map[jax.tree_util.keystr(p)] for p, _ in e_leaves])
    return red, new_err
