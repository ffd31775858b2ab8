"""Mesh construction (production + local).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before first jax init and only then calls it.

Axis roles:
  * pod   — inter-pod (DCN, slow links): batch parallelism + the compressed
            gradient all-reduce hop (dist/compressed_allreduce.py);
  * data  — in-pod FSDP axis: parameter/optimizer sharding + batch;
  * model — tensor parallel: heads / ffn / vocab / experts / KV-sequence.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    # all axes auto-partitioned (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Elastic entry point: any (pod, data, model) / (data, model) layout."""
    return _mk(shape, axes)


def make_local_mesh(model_parallel: int = 1, pods: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    data = n // (model_parallel * pods)
    assert data * model_parallel * pods == n, (n, pods, data, model_parallel)
    if pods > 1:
        return _mk((pods, data, model_parallel), ("pod", "data", "model"))
    return _mk((data, model_parallel), ("data", "model"))
