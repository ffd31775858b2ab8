"""Seeded device-side field generators.

Copies, in ``jax.numpy``, of the ``smooth`` and ``turbulent`` kinds of
``repro.data.fields`` (which builds them in numpy on the host: 16 s for one
512^3 field). Each variable is made on the device by one jitted call, so
set-up pays no host generation and no host-to-device copy. The program may
change its own generators; these stay with the benchmark.

Each field's structure comes from its configuration (``structure_seed``
and the field's index), so every seed compresses the same fields; the run's
``--seed`` draws the noise on top. Keys come from numpy's ``SeedSequence``,
which takes any non-negative integer, so seeds past 32 bits are fine.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, *path: int) -> jax.Array:
    """A threefry key from a seed of any size and a path of indices."""
    words = np.random.SeedSequence([seed % 2**64, *path]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@partial(jax.jit, static_argnames=("shape", "slope", "noise"))
def turbulent(structure: jax.Array, k: jax.Array, shape: tuple[int, ...],
              slope: float = 11.0 / 12.0, noise: float = 0.0):
    """White noise shaped by a ``(1 + |k|^2)^-slope`` spectrum (Nyx-like).
    ``structure`` draws that noise; ``k`` draws white noise of ``noise``
    times the field's standard deviation added on top."""
    white = jax.random.normal(structure, shape, jnp.float32)
    spec = jnp.fft.rfftn(white)
    k2 = jnp.zeros((), jnp.float32)
    for ax, s in enumerate(shape):
        last = ax == len(shape) - 1
        f = (jnp.fft.rfftfreq(s) if last else jnp.fft.fftfreq(s)) * s
        bshape = [1] * len(shape)
        bshape[ax] = f.size
        k2 = k2 + (f.astype(jnp.float32) ** 2).reshape(bshape)
    amp = (1.0 + k2) ** (-slope)
    field = jnp.fft.irfftn(spec * amp, s=shape).astype(jnp.float32)
    return field + noise * jnp.std(field) * jax.random.normal(k, shape, jnp.float32)


@partial(jax.jit, static_argnames=("shape", "terms", "noise"))
def smooth(structure: jax.Array, k: jax.Array, shape: tuple[int, ...],
           terms: int = 6, noise: float = 0.01):
    """Sums of separable low-frequency harmonics plus white noise
    (Hurricane-like). ``structure`` draws the harmonics' frequencies,
    phases and amplitudes; ``k`` draws the noise."""
    kf, kp, ka = jax.random.split(structure, 3)
    nd = len(shape)
    freqs = jax.random.uniform(kf, (terms, nd), jnp.float32, 0.5, 4.0)
    phase = jax.random.uniform(kp, (terms, nd), jnp.float32, 0.0, 2 * math.pi)
    amp = jax.random.uniform(ka, (terms,), jnp.float32, 0.2, 1.0)
    out = jnp.zeros(shape, jnp.float32)
    for t in range(terms):
        term = amp[t]
        for ax, s in enumerate(shape):
            g = jnp.linspace(0.0, 1.0, s, dtype=jnp.float32)
            bshape = [1] * nd
            bshape[ax] = s
            term = term * jnp.sin(2 * math.pi * freqs[t, ax] * g
                                  + phase[t, ax]).reshape(bshape)
        out = out + term
    return out + noise * jax.random.normal(k, shape, jnp.float32)


def make(variable: dict, shape: tuple[int, ...], seed: int, index: int) -> jax.Array:
    """One variable of a configuration: ``{"name", "kind", "params"}``.

    The field's structure comes from its ``structure_seed`` and index, so
    every seed runs the same fields (the same work); ``--seed`` draws the
    noise on top of them.
    """
    params = dict(variable.get("params", {}))
    kind = variable["kind"]
    if kind not in ("turbulent", "smooth"):
        raise ValueError(f"unknown field kind {kind!r}")
    structure = key(params.pop("structure_seed"), index)
    gen = turbulent if kind == "turbulent" else smooth
    return gen(structure, key(seed, index), tuple(shape), **params)
