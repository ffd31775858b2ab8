"""Bucketed overlapped reduce: plan stability, oracle parity, grad taps.

The multi-device (8 fake devices) parity and per-bucket HLO byte checks live
in tests/test_dist.py's slow subprocess; this file covers everything that
runs single-device: the deterministic bucket assignment (property-tested —
hypothesis wheel or the bundled minihypothesis fallback), bit parity of the
bucketed math against the ``reduce_stacked`` barrier oracle on the
reference (no-mesh) path, and the ``grad_boundary`` custom_vjp taps being
bit-exact identities under grad and vmap(grad).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.dist import bucketed_reduce as bkt
from repro.dist.compressed_allreduce import (GradCompressionConfig,
                                             init_error_state, reduce_stacked,
                                             wire_bytes_per_leaf)

SET = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# Bucket assignment
# ---------------------------------------------------------------------------

def _random_abstract_tree(seed: int):
    """Random nested dict of f32 ShapeDtypeStructs (mix of sizes/ranks)."""
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(int(rng.integers(1, 10))):
        nd = int(rng.integers(1, 4))
        shape = tuple(int(rng.integers(1, 33)) * (8 if d == 0 else 4)
                      for d in range(nd))
        tree[f"leaf{i:02d}"] = jax.ShapeDtypeStruct(shape, jnp.float32)
    if rng.integers(0, 2):      # sometimes a nested group
        tree["layers"] = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    return tree


@settings(**SET)
@given(st.integers(0, 10_000), st.sampled_from([1 << 12, 1 << 15, 1 << 20]))
def test_bucket_assignment_stable(seed, bucket_bytes):
    """Any leaf mix gets a deterministic, insertion-order-independent,
    exactly-once assignment that respects the byte target."""
    cfg = GradCompressionConfig(enabled=True, min_leaf_size=1024,
                                overlap=True, bucket_bytes=bucket_bytes)
    tree = _random_abstract_tree(seed)
    plan = bkt.assign_buckets(tree, cfg)
    # deterministic: same inputs -> identical plan (error feedback stays
    # aligned with its leaves across steps/restarts)
    assert plan == bkt.assign_buckets(tree, cfg)
    # dict insertion order is irrelevant (flatten sorts keys)
    shuffled = dict(reversed(list(tree.items())))
    assert plan == bkt.assign_buckets(shuffled, cfg)
    # every leaf lands exactly once: bucketed xor bypass
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    all_keys = {jax.tree_util.keystr(p) for p, _ in leaves}
    bucketed = [k for b in plan.buckets for k in b.keys]
    assert len(bucketed) == len(set(bucketed))
    assert set(bucketed) | set(plan.bypass) == all_keys
    assert not set(bucketed) & set(plan.bypass)
    # byte target: only a single oversized leaf may exceed it
    for b in plan.buckets:
        assert len(b.keys) == 1 or b.wire_bytes <= bucket_bytes
        assert b.wire_bytes == sum(
            wire_bytes_per_leaf(n, cfg)["compressed"] for n in b.n_elems)


def test_bucket_production_order():
    """Transformer top-level groups order unembed -> final_norm -> layers ->
    embed, and buckets are contiguous ranges of that order."""
    cfg = GradCompressionConfig(enabled=True, min_leaf_size=1024,
                                overlap=True, bucket_bytes=1)  # 1 leaf/bucket
    tree = {
        "embed": jax.ShapeDtypeStruct((256, 64), jnp.float32),
        "layers": {"wq": jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)},
        "final_norm": jax.ShapeDtypeStruct((4096,), jnp.float32),
        "unembed": jax.ShapeDtypeStruct((64, 256), jnp.float32),
    }
    plan = bkt.assign_buckets(tree, cfg)
    order = [k for b in plan.buckets for k in b.keys]
    assert order == ["['unembed']", "['final_norm']", "['layers']['wq']",
                     "['embed']"]
    assert [b.index for b in plan.buckets] == list(range(plan.n_buckets))
    assert plan.buckets[0].tag == "bucket0_reduce"


def test_small_and_nonfloat_leaves_bypass():
    cfg = GradCompressionConfig(enabled=True, min_leaf_size=4096, overlap=True)
    tree = {"big": jax.ShapeDtypeStruct((4096,), jnp.float32),
            "small": jax.ShapeDtypeStruct((16,), jnp.float32),
            "ints": jax.ShapeDtypeStruct((8192,), jnp.int32)}
    plan = bkt.assign_buckets(tree, cfg)
    assert set(plan.bypass) == {"['small']", "['ints']"}
    assert [b.keys for b in plan.buckets] == [("['big']",)]


def test_gathered_bytes_vs_wire_bytes():
    """The DCE-aware byte model differs from the wire model by exactly the
    two bookkeeping scalars the mean hop never reads (grad config keeps
    exact_outliers off, so the outlier side-channel is empty)."""
    cfg = GradCompressionConfig(enabled=True)
    for n in (1 << 12, 1 << 16):
        wire = wire_bytes_per_leaf(n, cfg)["compressed"]
        gathered = bkt.gathered_bytes_per_leaf(n, cfg)
        assert gathered == wire - 8
    plan = bkt.assign_buckets({"w": jax.ShapeDtypeStruct((1 << 14,), jnp.float32)},
                              cfg)
    exp = bkt.expected_cross_pod_bytes(plan, cfg, n_pods=4)
    assert exp == {"bucket0_reduce": 4 * bkt.gathered_bytes_per_leaf(1 << 14, cfg)}


# ---------------------------------------------------------------------------
# Oracle parity (reference no-mesh path; the mesh path is in test_dist.py)
# ---------------------------------------------------------------------------

def _grad_tree(rng, step):
    scale = 1.0 + 0.25 * step
    return {"layers": {"wq": jnp.asarray(rng.standard_normal((2, 64, 64)).astype(np.float32)) * scale,
                       "wk": jnp.asarray(rng.standard_normal((2, 32, 64)).astype(np.float32)) * scale},
            "unembed": jnp.asarray(rng.standard_normal((2, 64, 64)).astype(np.float32)) * scale,
            "bias": jnp.asarray(rng.standard_normal((2, 8)).astype(np.float32)) * scale}


@pytest.mark.parametrize("bucket_bytes", [1, 1 << 30])
def test_bucketed_bit_identical_to_barrier_reference(bucket_bytes):
    """3 steps of error feedback: reduced grads AND error state bit-identical
    to the barrier oracle, whether every leaf gets its own bucket or all
    leaves share one — per-leaf math is unchanged by the issue granularity."""
    gc = GradCompressionConfig(enabled=True, eb=1e-4, min_leaf_size=1024)
    gcb = GradCompressionConfig(enabled=True, eb=1e-4, min_leaf_size=1024,
                                overlap=True, bucket_bytes=bucket_bytes)
    rng = np.random.default_rng(7)
    g0 = _grad_tree(rng, 0)
    g_abs = jax.tree.map(lambda g: jax.ShapeDtypeStruct(g.shape[1:], g.dtype), g0)
    plan = bkt.assign_buckets(g_abs, gcb)
    if bucket_bytes == 1:
        assert plan.n_buckets == 3       # one compressible leaf per bucket
    else:
        assert plan.n_buckets == 1
    err_a = init_error_state(g_abs, 2, gc)
    err_b = init_error_state(g_abs, 2, gcb)
    for step in range(3):
        g = _grad_tree(np.random.default_rng(7), step)
        red_a, err_a = reduce_stacked(g, err_a, gc)
        red_b, err_b = bkt.reduce_stacked_bucketed(g, err_b, gcb, plan=plan)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), red_a, red_b)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), err_a, err_b)


def test_disabled_config_is_exact_mean():
    gc = GradCompressionConfig(enabled=False)
    rng = np.random.default_rng(3)
    g = _grad_tree(rng, 0)
    red, err = bkt.reduce_stacked_bucketed(g, {}, gc)
    jax.tree.map(lambda r, x: np.testing.assert_allclose(
        np.asarray(r), np.asarray(jnp.mean(x, 0)), rtol=1e-6), red, g)
    assert err == {}


# ---------------------------------------------------------------------------
# grad_boundary taps
# ---------------------------------------------------------------------------

def test_grad_boundary_is_bit_exact_identity():
    """Arming the taps changes neither the loss nor any gradient bit: the
    boundary is a custom_vjp identity whose backward only pins scheduling
    (optimization_barrier), under plain grad and under vmap(grad) — the
    step builder's pod vmap relies on the barrier's own batching rule."""
    from repro import configs
    from repro.models import nn, zoo

    cfg = configs.get("glm4-9b", smoke=True)
    model = zoo.build(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16), dtype=np.int32)),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16), dtype=np.int32))}

    def loss(p, b):
        return model.train_loss(p, b)[0]

    split = jax.tree.map(lambda x: x.reshape((2, 2) + x.shape[1:]), batch)

    def run():
        # fresh jit wrappers each call: the tap is a trace-time global, so a
        # cached trace from the un-tapped run must not be reused
        l = jax.jit(loss)(params, batch)
        g = jax.jit(jax.grad(loss))(params, batch)
        v = jax.jit(jax.vmap(jax.grad(loss), in_axes=(None, 0)))(params, split)
        return l, g, v

    base_l, base_g, base_v = run()
    nn.set_grad_tap(bkt.grad_boundary)
    try:
        tap_l, tap_g, tap_v = run()
    finally:
        nn.set_grad_tap(None)
    np.testing.assert_array_equal(np.asarray(base_l), np.asarray(tap_l))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), base_g, tap_g)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), base_v, tap_v)
