"""Faults planted in a training cell's timed path, to show that the check
catches them. Each takes a ``setattr(obj, name, value)`` (pytest's
``monkeypatch.setattr``) and breaks the program underneath the harness:

- ``own_pod``: each pod keeps its own gradient: it compresses and decodes
  its own container and takes no other pod's;
- ``exchange_skipped``: the exchange between chips left out: each pod's hop
  hands back its own gradient as it is, uncompressed;
- ``embed_dropped``: the embedding's reduced gradient is zeroed;
- ``stale``: a step that returns its state unchanged (the loss is still
  computed);
- ``half_batch``: half of the batch left out, the mean taken over the rest:
  the second half of every batch's rows repeats the first.
"""
from __future__ import annotations


def own_pod(setattr_):
    from repro.core import fz
    from repro.dist import compressed_allreduce as car

    def body(xi, fzc):
        mine = fz.decompress(fz.compress(xi, fzc), fzc)
        return mine, (xi - mine)[None]
    setattr_(car, "pod_hop_body", body)


def exchange_skipped(setattr_):
    import jax.numpy as jnp
    from repro.dist import compressed_allreduce as car
    setattr_(car, "pod_hop_body", lambda xi, fzc: (xi, jnp.zeros_like(xi)[None]))


def embed_dropped(setattr_):
    import jax.numpy as jnp
    from repro.dist import compressed_allreduce as car
    real = car.reduce_stacked

    def reduce(*args, **kwargs):
        red, err = real(*args, **kwargs)
        return dict(red, embed=jnp.zeros_like(red["embed"])), err
    setattr_(car, "reduce_stacked", reduce)


def stale(setattr_):
    import jax
    from repro.train import trainer
    real = trainer.build_train_step

    def build(*args, **kwargs):
        fn, info = real(*args, **kwargs)
        return jax.jit(lambda p, o, e, i, b: (p, o, e, fn(p, o, e, i, b)[3])), info
    setattr_(trainer, "build_train_step", build)


def half_batch(setattr_):
    from repro.train.trainer import Trainer
    real = Trainer._batch

    def batch(self, step):
        out = real(self, step)
        half = out["tokens"].shape[0] // 2
        return {k: v.at[half:].set(v[:half]) for k, v in out.items()}
    setattr_(Trainer, "_batch", batch)


FAULTS = {f.__name__: f for f in (own_pod, exchange_skipped, embed_dropped, stale,
                                  half_batch)}
