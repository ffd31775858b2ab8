"""The plain reference of the training cells: weights, batches and steps.

What a training cell's configuration states, written out again in plain
``jax.numpy`` from the published description, importing nothing of the
program and taking nothing it made:

- the model: Llama's decoder as Yi-6B publishes it: token embedding, per
  layer a pre-RMSNorm grouped-query attention with rotary positions
  (``rope_theta``; the rotation pairs dimension ``i`` with ``i + hd/2``) and
  a causal softmax, then a pre-RMSNorm SwiGLU feed-forward, a final
  RMSNorm, an untied unembedding and the mean token cross-entropy. Weights
  and activations are bfloat16 as the configuration's ``torch_dtype``
  states; every matrix product accumulates in float32 and rounds its
  result to bfloat16, norms, rotations, softmax and loss run in float32;
- the optimizer: AdamW with float32 master weights and moments, clipped to
  a global gradient norm of 1, and the traffic's warm-up and cosine
  schedule;
- the exchange, as the configuration states it: each pod takes its
  share of the batch's rows; a floating leaf of at least ``min_leaf_size``
  elements crosses the pods as the error-bounded reconstruction of the
  pod's gradient plus its carried residual (every value the nearest
  multiple of twice the bound that FZ states for that input, so
  within the bound of it), the residual keeps the difference, and the
  reduced gradient is the mean of the pods' reconstructions; smaller
  leaves are averaged exactly. The loss is the mean of the pods' losses.

``init_params`` and ``batch`` are also what the benchmark feeds the
program, so both sides start from the same weights and see the same rows.

The control (``precision="fp8"``) computes every matrix product of the
reference from operands rounded to 8-bit e4m3 floats with a per-tensor
scale (the backward pass multiplies by the rounded operands): the
precision below the configuration's bfloat16. The faults
(``fault=...``) break one guarantee of the exchange in the reference's
place: ``half`` takes loss and gradient over half the batch,
``own_pod`` keeps pod 0's gradient (its half of the rows) while the loss
covers the whole batch, ``embed_dropped`` zeroes the embedding's gradient.
"""
from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .fields import key

B1, B2, EPS, WEIGHT_DECAY, CLIP = 0.9, 0.95, 1e-8, 0.1, 1.0
FAULTS = ("half", "own_pod", "embed_dropped")


def dims(conf: dict) -> dict:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {"d": d, "h": h, "kv": conf["num_key_value_heads"], "hd": d // h,
            "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "layers": conf["num_hidden_layers"], "theta": float(conf["rope_theta"]),
            "eps": float(conf["rms_norm_eps"])}


def param_shapes(conf: dict) -> dict:
    """Leaf shapes, layers stacked on a leading axis."""
    a = dims(conf)
    L, d, ff = a["layers"], a["d"], a["ff"]
    qd, kvd = a["h"] * a["hd"], a["kv"] * a["hd"]
    return {"embed": (a["vocab"], d),
            "layers": {"attn_norm": (L, d), "wq": (L, d, qd), "wk": (L, d, kvd),
                       "wv": (L, d, kvd), "wo": (L, qd, d), "mlp_norm": (L, d),
                       "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)},
            "final_norm": (d,), "unembed": (d, a["vocab"])}


def leaf_names(conf: dict) -> list[str]:
    """Each leaf's path, in ``jax.tree.leaves`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        param_shapes(conf), is_leaf=lambda s: isinstance(s, tuple))
    return [jax.tree_util.keystr(p) for p, _ in flat]


def _init(k, shapes):
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    out = []
    for i, (path, shape) in enumerate(zip(paths, leaves)):
        if "norm" in path:
            out.append(jnp.ones(shape, jnp.bfloat16))
            continue
        std = 0.02 if "embed" in path and "unembed" not in path else shape[-2] ** -0.5
        w = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32) * std
        out.append(w.astype(jnp.bfloat16))
    return jax.tree.unflatten(tree, out)


def init_params(conf: dict, seed: int, shardings=None) -> dict:
    """The seeded bfloat16 weights, made on the device in one jitted call
    (placed by ``shardings`` where given)."""
    shapes = param_shapes(conf)
    fn = jax.jit(partial(_init, shapes=shapes), out_shardings=shardings)
    return fn(key(seed, 0))


def batch(vocab: int, seq_len: int, rows: int, seed: int, step: int) -> np.ndarray:
    """``(rows, seq_len + 1)`` int32 tokens of one step: Zipf(1.3) draws,
    each token with probability 1/2 replaced by its predecessor + 1
    (a copy of the rule of the program's ``TokenStream``). Rows are keyed
    by (seed, step, row), so every row of every step differs."""
    out = np.empty((rows, seq_len + 1), np.int32)
    t = np.arange(seq_len + 1)
    for i in range(rows):
        rng = np.random.Generator(np.random.Philox(key=seed % 2**64,
                                                   counter=[0, 0, step, i]))
        base = (rng.zipf(1.3, size=seq_len + 1).astype(np.int64) - 1) % vocab
        rep = rng.random(seq_len + 1) < 0.5
        rep[0] = False
        start = np.maximum.accumulate(np.where(rep, 0, t))
        out[i] = ((base[start] + t - start) % vocab).astype(np.int32)
    return out


def schedule(step: int, peak_lr: float, warmup_steps: int, total_steps: int,
             floor: float = 0.1) -> float:
    """Linear warm-up from 0, then cosine from ``peak_lr`` to ``floor`` of it."""
    if step < warmup_steps:
        return peak_lr * step / max(warmup_steps, 1)
    prog = min(max((step - warmup_steps) / max(total_steps - warmup_steps, 1), 0.0), 1.0)
    return peak_lr * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * prog)))


def _fp8(x):
    """``x`` rounded to 8-bit floats (4 exponent and 3 mantissa bits, e4m3)
    under a per-tensor scale that maps its largest magnitude to 240, the
    format's largest finite value, back in its dtype; the gradient passes
    the rounding unchanged. ``reduce_precision`` rounds where a cast to
    float8 and back may be dropped by the compiler as excess precision."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 240.0
    q = jax.lax.reduce_precision(xf / s, exponent_bits=4, mantissa_bits=3) * s
    return (xf + jax.lax.stop_gradient(q - xf)).astype(x.dtype)


def _dot(x, w, precision):
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    y = jnp.einsum("...i,io->...o", x, w, preferred_element_type=jnp.float32)
    return y.astype(jnp.bfloat16)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(jnp.bfloat16) * scale


def _rope(x, theta):
    """x: (B, S, heads, hd); rotation of dimension i with i + hd/2."""
    hd = x.shape[-1]
    freq = theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2].astype(jnp.float32), x[..., hd // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def loss(params: dict, tokens, labels, a: dict, precision: str = "bf16"):
    """Mean next-token cross-entropy of the decoder."""
    B, S = tokens.shape
    h, kv, hd = a["h"], a["kv"], a["hd"]
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(a["layers"]):
        p = {k: v[i] for k, v in params["layers"].items()}
        y = _rms(x, p["attn_norm"], a["eps"])
        q = _rope(_dot(y, p["wq"], precision).reshape(B, S, h, hd), a["theta"])
        k = _rope(_dot(y, p["wk"], precision).reshape(B, S, kv, hd), a["theta"])
        v = _dot(y, p["wv"], precision).reshape(B, S, kv, hd)
        q = q.reshape(B, S, kv, h // kv, hd).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k.astype(jnp.float32)) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
        x = x + _dot(o.reshape(B, S, h * hd).astype(jnp.bfloat16), p["wo"], precision)
        y = _rms(x, p["mlp_norm"], a["eps"])
        g = jax.nn.silu(_dot(y, p["w_gate"], precision)) * _dot(y, p["w_up"], precision)
        x = x + _dot(g, p["w_down"], precision)
    logits = _dot(_rms(x, params["final_norm"], a["eps"]), params["unembed"],
                  precision).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def bound(x, eb: float, eb_mode: str):
    """The exchange's absolute bound on one pod's input, as FZ states it
    (``bench/reference.py``): ``eb`` times the larger of the range and the
    largest magnitude in ``rel`` mode, floored at 1e-30, cut to 8
    significant bits."""
    if eb_mode == "abs":
        e = jnp.float32(eb)
    else:
        e = jnp.float32(eb) * jnp.maximum(jnp.max(x) - jnp.min(x), jnp.max(jnp.abs(x)))
        e = jnp.maximum(e, jnp.float32(1e-30))
    bits = jax.lax.bitcast_convert_type(e, jnp.int32) & ~0xFFFF
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def exchange(grads: list, err, ex: dict, fault: str | None):
    """The pods' gradients (one tree each) reduced as the configuration
    states: a leaf that is floating and holds at least ``min_leaf_size``
    elements crosses as its error-bounded reconstruction (every value the
    nearest multiple of twice the bound), of the gradient plus the pod's
    carried residual, which keeps the rest; the mean of the pods'
    reconstructions is cast to the leaf's dtype. Smaller leaves: the exact
    mean. Returns the reduced tree and the new residuals."""
    n = len(grads)

    def one(e, *gs):
        if e.size == 0:
            red = sum(g.astype(jnp.float32) for g in gs) / n
            return red.astype(gs[0].dtype), e
        xs = [g.astype(jnp.float32) + e[p] for p, g in enumerate(gs)]
        recs = []
        for x in xs:
            two = 2.0 * bound(x, ex["eb"], ex["eb_mode"])
            recs.append(jnp.round(x / two) * two)
        kept = recs[:1] if fault in ("half", "own_pod") else recs
        red = sum(kept) / len(kept)
        return red.astype(gs[0].dtype), jnp.stack([x - r for x, r in zip(xs, recs)])

    out = jax.tree.map(one, err, *grads)
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1)


def _step(params, opt, err, tokens, labels, lr, a, ex, pods, precision, fault):
    rows = tokens.shape[0] // pods
    parts = [jax.value_and_grad(loss)(params, tokens[p * rows:(p + 1) * rows],
                                      labels[p * rows:(p + 1) * rows], a, precision)
             for p in range(pods)]
    value = parts[0][0] if fault == "half" else sum(v for v, _ in parts) / pods
    grads, err = exchange([g for _, g in parts], err, ex, fault)
    if fault == "embed_dropped":
        grads = dict(grads, embed=jnp.zeros_like(grads["embed"]))
    count = opt["count"] + 1
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, CLIP / jnp.maximum(gnorm, 1e-9))
    c1 = 1.0 - B1 ** count.astype(jnp.float32)
    c2 = 1.0 - B2 ** count.astype(jnp.float32)

    def one(g, m, v, w):
        g = g.astype(jnp.float32) * scale
        m = B1 * m + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        w = w - lr * ((m / c1) / (jnp.sqrt(v / c2) + EPS) + WEIGHT_DECAY * w)
        return m, v, w

    out = jax.tree.map(one, grads, opt["m"], opt["v"], opt["master"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    opt = {"m": pick(0), "v": pick(1), "master": pick(2), "count": count}
    new = jax.tree.map(lambda w: w.astype(jnp.bfloat16), opt["master"])
    return new, opt, err, value


@functools.cache
def _step_fn(a: tuple, ex: tuple, pods: int, precision: str, fault: str | None):
    """One jitted reference step per variant, kept for the process."""
    return jax.jit(partial(_step, a=dict(a), ex=dict(ex), pods=pods,
                           precision=precision, fault=fault), donate_argnums=(0, 1, 2))


@jax.jit
def _changed(master, init):
    return leaf_norms(jax.tree.map(jnp.subtract, master, init))


def leaf_norms(tree) -> jax.Array:
    """Float32 norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def shardings(shapes: dict, devices) -> dict:
    """Each leaf split on its largest axis over ``devices`` where that axis
    divides, else kept whole on each: so the reference fits beside nothing."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("ref",))
    n = len(devices)

    def one(shape):
        ax = int(np.argmax(shape))
        spec = [None] * len(shape)
        if shape[ax] % n == 0:
            spec[ax] = "ref"
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(one, shapes, is_leaf=lambda s: isinstance(s, tuple))


def compressible(shape: tuple, min_leaf_size: int) -> bool:
    """A (floating) leaf that crosses the pods compressed."""
    return math.prod(shape) >= min_leaf_size


def readings(conf: dict, traffic: dict, seed: int, devices, precision: str = "bf16",
             fault: str | None = None) -> dict:
    """The reference's first ``checked_steps`` steps from ``seed``: the
    losses, each leaf's norm of the first gradient as the optimizer takes
    it (clipped), and each leaf's norm of the master weights' change
    after the last step. Everything is made here, on ``devices``."""
    a = dims(conf)
    pods = conf["mesh"]["pod"]
    ex = {k: traffic["grad_compress"][k] for k in ("eb", "eb_mode", "min_leaf_size")}
    shapes = param_shapes(conf)
    sh = shardings(shapes, devices)
    params = init_params(conf, seed, sh)
    master = jax.jit(lambda p: jax.tree.map(lambda w: w.astype(jnp.float32), p),
                     out_shardings=sh)
    zeros = jax.jit(lambda p: jax.tree.map(
        lambda w: jnp.zeros(w.shape, jnp.float32), p), out_shardings=sh)
    err_shapes = jax.tree.map(
        lambda s: (pods, *s) if compressible(s, ex["min_leaf_size"]) else (0,), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    err = jax.jit(lambda: jax.tree.map(lambda s: jnp.zeros(s, jnp.float32), err_shapes,
                                       is_leaf=lambda s: isinstance(s, tuple)),
                  out_shardings=shardings(err_shapes, devices))()
    init_master = master(params)          # kept: the steps donate their state
    opt = {"m": zeros(params), "v": zeros(params), "master": master(params),
           "count": jnp.zeros((), jnp.int32)}
    sched = traffic["schedule"]
    step = _step_fn(tuple(sorted(a.items())), tuple(sorted(ex.items())), pods,
                    precision, fault)
    with jax.default_matmul_precision("highest"):
        losses, first = [], None
        for i in range(int(traffic["checked_steps"])):
            rows = batch(a["vocab"], traffic["seq_len"], traffic["global_batch"], seed, i)
            lr = jnp.float32(schedule(i, sched["peak_lr"], sched["warmup_steps"],
                                      sched["total_steps"]))
            params, opt, err, value = step(params, opt, err, jnp.asarray(rows[:, :-1]),
                                           jnp.asarray(rows[:, 1:]), lr)
            losses.append(float(value))
            if first is None:
                first = np.asarray(leaf_norms(opt["m"])) / (1 - B1)
        change = np.asarray(_changed(opt["master"], init_master))
    return {"losses": losses, "grad_norms": first, "change_norms": change}
