"""Logical-axis sharding resolution: model declarations -> mesh layouts.

Models (models/nn.py) declare per-dimension *logical* axes and never see a
mesh. This module owns the logical vocabulary and resolves it against any
concrete mesh:

  * ``fsdp`` — parameter/optimizer sharding (ZeRO-style);
  * ``tp``   — tensor parallel (heads / ffn / vocab / experts);
  * ``dp``   — batch parallelism for activations and inputs; spans
    ``(pod, data)`` on multi-pod meshes so the global batch covers both
    the DCN and the in-pod FSDP axes;
  * ``None`` — replicated.

Resolution rules (pinned by tests/test_dist.py):
  * a dimension shards only if its size is divisible by the product of the
    assigned mesh axes; otherwise the assignment falls back toward
    replication by dropping leading mesh axes (so ``dp`` degrades from
    ``(pod, data)`` to ``(data,)`` to replicated);
  * a mesh axis is used at most once per spec (first dimension wins);
  * unknown logical names and mesh axes absent from the mesh resolve to
    replication, never to an error — elastic resharding (ckpt/elastic.py)
    depends on every (spec, mesh) pair being resolvable.

``set_profile`` flips the parameter-layout profile the dry-run measures:
``"tp"`` (default) keeps tensor parallelism on the model axis; ``"zero3"``
turns the model axis into extra fully-sharded data parallelism (params
sharded over (data, model), tp dims replicated, batch over every axis).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_PROFILES = ("tp", "zero3")
_profile = "tp"


def set_profile(name: str) -> None:
    """Select the parameter-layout profile ("tp" | "zero3")."""
    global _profile
    if name not in _PROFILES:
        raise ValueError(f"unknown sharding profile {name!r}; want one of {_PROFILES}")
    _profile = name


def get_profile() -> str:
    return _profile


def logical_to_mesh_axes(mesh) -> dict[str, tuple[str, ...]]:
    """The logical-name -> mesh-axes table for ``mesh`` under the profile.

    Only axis *names* are consulted, so this works on abstract stand-in
    meshes as well as real ones.
    """
    names = tuple(mesh.axis_names)
    dp = ("pod", "data") if "pod" in names else ("data",)
    if _profile == "zero3":
        table = {"fsdp": ("data", "model"), "tp": (), "dp": dp + ("model",)}
    else:
        table = {"fsdp": ("data",), "tp": ("model",), "dp": dp}
    return {k: tuple(a for a in v if a in names) for k, v in table.items()}


def _mesh_axis_size(mesh, axis: str) -> int:
    return int(mesh.shape[axis])


def resolve_spec(logical: Sequence[str | None], shape: Sequence[int], mesh,
                 exclude: Sequence[str] = ()) -> P:
    """Resolve a per-dimension logical spec into a PartitionSpec for ``mesh``.

    Divisibility fallback: for each dimension, the longest suffix of the
    assigned mesh-axis tuple whose total size divides the dimension is used
    (suffix, so ``dp`` prefers the large in-pod ``data`` axis over ``pod``
    when the full span does not divide); no suffix divides -> replicated.
    Mesh axes in ``exclude`` are taken already (e.g. ``pod`` inside a vmap
    over pods whose mapped dimension carries it).
    """
    table = logical_to_mesh_axes(mesh)
    used: set[str] = set(exclude)
    entries: list[Any] = []
    for i, dim in enumerate(shape):
        name = logical[i] if i < len(logical) else None
        if name is None:
            entries.append(None)
            continue
        axes = tuple(a for a in table.get(name, ()) if a not in used)
        chosen: tuple[str, ...] = ()
        for start in range(len(axes)):
            cand = axes[start:]
            size = 1
            for a in cand:
                size *= _mesh_axis_size(mesh, a)
            if size > 1 and dim % size == 0:
                chosen = cand
                break
        if not chosen:
            entries.append(None)
            continue
        used.update(chosen)
        entries.append(chosen[0] if len(chosen) == 1 else chosen)
    return P(*entries)


def _is_logical_leaf(x) -> bool:
    return x is None or (isinstance(x, tuple)
                         and all(e is None or isinstance(e, str) for e in x))


def tree_shardings(logical_tree: Any, abstract_tree: Any, mesh) -> Any:
    """NamedSharding pytree for ``abstract_tree`` laid out per ``logical_tree``.

    ``logical_tree`` mirrors ``abstract_tree`` with tuple-of-logical-names
    leaves (``()`` for scalars); ``abstract_tree`` carries anything with a
    ``.shape`` (arrays or ShapeDtypeStructs).
    """
    def one(spec, ab):
        spec = () if spec is None else tuple(spec)
        return NamedSharding(mesh, resolve_spec(spec, tuple(ab.shape), mesh))
    return jax.tree.map(one, logical_tree, abstract_tree, is_leaf=_is_logical_leaf)
