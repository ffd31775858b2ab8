"""Train / serve step builders: sharded, jit-able, dry-run-lowerable.

``build_train_step`` assembles the full production step:
  microbatch gradient accumulation (scan) -> optional FZ-compressed cross-pod
  gradient all-reduce with error feedback (manual 'pod' axis via hybrid
  shard_map; in-pod collectives stay XLA-automatic) -> global-norm clip ->
  AdamW with f32 master/moments sharded like the params.

``build_prefill_step`` / ``build_decode_step`` are the serving analogues.
All builders return (fn, in_shardings, out_shardings, input_structs) so the
same artifacts serve training, serving, and the dry-run compiler.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ShapeConfig
from repro.dist import bucketed_reduce as bkt
from repro.dist import compressed_allreduce as car
from repro.dist import sharding as shd
from repro.models import nn, zoo
from repro.optim import AdamWConfig, adamw_init, adamw_update, global_norm, warmup_cosine


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    microbatches: int = 1              # gradient-accumulation steps
    adamw: AdamWConfig = AdamWConfig()
    grad_compress: car.GradCompressionConfig = car.GradCompressionConfig(enabled=False)


def _named(mesh, spec_tree_, abstract_tree):
    return shd.tree_shardings(spec_tree_, abstract_tree, mesh)


def _install_act_sharder(mesh, exclude: tuple[str, ...] = ()) -> None:
    """Route model-side nn.shard_act calls to this mesh (trace-time global).
    Mesh axes in ``exclude`` are left to an enclosing vmap's
    ``spmd_axis_name``. Like the grad tap, each train step installs its own
    at the top of its body."""

    def sharder(x, logical):
        spec = shd.resolve_spec(tuple(logical), x.shape, mesh, exclude)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    nn.set_act_sharder(sharder)


def _install_grad_tap(overlap: bool) -> None:
    """Arm (or disarm) the model-side gradient-boundary taps: with the
    overlapped bucketed reduce, each parameter group's cotangents pass
    through an optimization_barrier so the per-bucket hops see real
    boundaries. The tap is a trace-time global (same idiom as the act
    sharder) but jit traces lazily, so each step function calls this at the
    TOP OF ITS OWN BODY — building several steps in any order and calling
    them later still traces each with its own tap state."""
    nn.set_grad_tap(bkt.grad_boundary if overlap else None)


def _loss_and_grads(model: zoo.Model, params, batch, n_micro: int):
    """Gradient accumulation over ``n_micro`` microbatches via scan."""
    if n_micro == 1:
        (loss, aux), grads = jax.value_and_grad(model.train_loss, has_aux=True)(params, batch)
        return loss, grads

    def split(x):
        b = x.shape[0]
        return x.reshape((n_micro, b // n_micro) + x.shape[1:])

    micro = jax.tree.map(split, batch)

    def acc_step(carry, mb):
        loss_acc, g_acc = carry
        (loss, _), g = jax.value_and_grad(model.train_loss, has_aux=True)(params, mb)
        g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
        return (loss_acc + loss, g_acc), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grads), _ = jax.lax.scan(acc_step, (jnp.float32(0), g0), micro)
    inv = 1.0 / n_micro
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, grads)


def build_train_step(model: zoo.Model, shape: ShapeConfig, mesh, tcfg: TrainConfig):
    """Returns (step_fn, state_shardings, input structs/shardings).

    step(params, opt_state, err_state, step_idx, batch)
      -> (params, opt_state, err_state, metrics)
    """
    _install_act_sharder(mesh)
    cfg = model.cfg
    specs = model.param_specs()
    abstract = model.abstract_params()
    param_sh = _named(mesh, specs, abstract)
    opt_abstract = jax.eval_shape(adamw_init, abstract)
    opt_specs = {
        "m": specs, "v": specs, "master": specs,
        "count": (),
    }
    opt_sh = {
        "m": _named(mesh, specs, opt_abstract["m"]),
        "v": _named(mesh, specs, opt_abstract["v"]),
        "master": _named(mesh, specs, opt_abstract["master"]),
        "count": NamedSharding(mesh, P()),
    }
    in_structs, in_logical = model.input_specs(shape)
    batch_sh = {k: NamedSharding(mesh, shd.resolve_spec(in_logical[k], v.shape, mesh))
                for k, v in in_structs.items()}

    use_pod_compress = tcfg.grad_compress.enabled and "pod" in mesh.axis_names
    overlap = use_pod_compress and tcfg.grad_compress.overlap
    n_pods = mesh.shape.get("pod", 1)

    def _finish(loss, grads, params, opt_state, step_idx):
        lr = warmup_cosine(step_idx, peak_lr=tcfg.peak_lr,
                           warmup_steps=tcfg.warmup_steps, total_steps=tcfg.total_steps)
        new_params, new_opt = adamw_update(grads, opt_state, lr, tcfg.adamw, params)
        metrics = {"loss": loss, "lr": lr, "grad_norm": global_norm(grads)}
        return new_params, new_opt, metrics

    if use_pod_compress:
        # per-pod gradients via vmap over a leading pod dim (loss/backward
        # stay pure-auto SPMD); the reduce hops themselves are manual
        # shard_maps over 'pod' with error feedback. Barrier form (one hop
        # per leaf after the full backward): dist/compressed_allreduce.py;
        # overlap form (size-targeted buckets issued in backward production
        # order, grad_boundary taps armed): dist/bucketed_reduce.py.
        plan = bkt.assign_buckets(abstract, tcfg.grad_compress) if overlap else None

        def step(params, opt_state, err_state, step_idx, batch):
            _install_grad_tap(overlap)   # runs at trace time, see helper
            # each pod's activations stay on that pod: the vmapped dim is
            # sharded over 'pod' (spmd_axis_name) and the model's own
            # constraints use the in-pod axes only. Left to the act sharder,
            # 'dp' would spread one pod's batch over both pods, and every
            # per-pod gradient would be all-reduced across pods in full
            # before its compressed hop.
            _install_act_sharder(mesh, exclude=("pod",))

            def split(x):
                b = x.shape[0]
                return x.reshape((n_pods, b // n_pods) + x.shape[1:])

            pods_batch = jax.tree.map(split, batch)

            def pod_loss(p, b):
                l, g = _loss_and_grads(model, p, b, tcfg.microbatches)
                return l, g

            losses, grads_stacked = jax.vmap(
                pod_loss, in_axes=(None, 0), spmd_axis_name="pod")(params, pods_batch)
            if overlap:
                grads, err_state = bkt.reduce_stacked_bucketed(
                    grads_stacked, err_state, tcfg.grad_compress, mesh, plan=plan)
            else:
                grads, err_state = car.reduce_stacked(grads_stacked, err_state,
                                                      tcfg.grad_compress, mesh)
            p, o, m = _finish(jnp.mean(losses), grads, params, opt_state, step_idx)
            return p, o, err_state, m

        # batch leading dim shards over (pod, data); after the split-reshape the
        # pod factor aligns with the new leading axis
        err_sh_fn = lambda ga: car.error_state_shardings(ga, tcfg.grad_compress, mesh)
    else:
        def step(params, opt_state, err_state, step_idx, batch):
            _install_grad_tap(False)     # runs at trace time, see helper
            _install_act_sharder(mesh)
            loss, grads = _loss_and_grads(model, params, batch, tcfg.microbatches)
            p, o, m = _finish(loss, grads, params, opt_state, step_idx)
            return p, o, err_state, m

        err_sh_fn = None

    def make_err_state(grads_abstract):
        if not use_pod_compress:   # no pod axis -> step never reads err
            return {}
        return car.init_error_state(grads_abstract, n_pods, tcfg.grad_compress)

    jitted = jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, None, NamedSharding(mesh, P()), batch_sh),
        out_shardings=(param_sh, opt_sh, None, None),
        donate_argnums=(0, 1, 2),
    )
    return jitted, dict(params=param_sh, opt=opt_sh, batch=batch_sh,
                        input_structs=in_structs, make_err_state=make_err_state,
                        err_shardings=err_sh_fn)


def build_prefill_step(model: zoo.Model, shape: ShapeConfig, mesh):
    _install_act_sharder(mesh)
    cfg = model.cfg
    param_sh = _named(mesh, model.param_specs(), model.abstract_params())
    in_structs, in_logical = model.input_specs(shape)
    batch_sh = {k: NamedSharding(mesh, shd.resolve_spec(in_logical[k], v.shape, mesh))
                for k, v in in_structs.items()}
    cache_abs, cache_logical = model.cache_specs(shape)
    cache_sh = {k: NamedSharding(mesh, shd.resolve_spec(cache_logical[k], v.shape, mesh))
                for k, v in cache_abs.items()}

    def prefill(params, batch):
        return model.prefill(params, batch)

    jitted = jax.jit(prefill, in_shardings=(param_sh, batch_sh),
                     out_shardings=(None, cache_sh))
    return jitted, dict(params=param_sh, batch=batch_sh, cache=cache_sh,
                        input_structs=in_structs, cache_structs=cache_abs)


def build_decode_step(model: zoo.Model, shape: ShapeConfig, mesh):
    _install_act_sharder(mesh)
    cfg = model.cfg
    param_sh = _named(mesh, model.param_specs(), model.abstract_params())
    in_structs, in_logical = model.input_specs(shape)
    tok_sh = {k: NamedSharding(mesh, shd.resolve_spec(in_logical[k], v.shape, mesh))
              for k, v in in_structs.items()}
    cache_abs, cache_logical = model.cache_specs(shape)
    cache_sh = {k: NamedSharding(mesh, shd.resolve_spec(cache_logical[k], v.shape, mesh))
                for k, v in cache_abs.items()}

    def decode(params, cache, inputs):
        return model.decode(params, cache, inputs["token"], inputs.get("positions"))

    jitted = jax.jit(decode, in_shardings=(param_sh, cache_sh, tok_sh),
                     out_shardings=(None, cache_sh), donate_argnums=(1,))
    return jitted, dict(params=param_sh, cache=cache_sh, batch=tok_sh,
                        input_structs=in_structs, cache_structs=cache_abs)
