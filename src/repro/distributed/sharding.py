"""Mesh context + logical sharding helpers.

Axis conventions (DESIGN.md §5):
  - ``pod``   cross-pod data parallelism (outermost)
  - ``data``  in-pod data parallelism (batch, optimizer ZeRO shards)
  - ``model`` tensor/expert parallelism (heads, FFN, experts, vocab rows)

Models call :func:`constrain` with *logical* axes; axes absent from the active
mesh are dropped, so the same model code runs on a single CPU device, a 16x16
pod, and the 2x16x16 multi-pod mesh.  The active mesh is installed by the
launcher via :func:`set_mesh` (a context manager) — a deliberate, documented
global so model code stays mesh-agnostic (the MaxText/ flax logical-axis
pattern without the flax dependency).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_MESH: Optional[Mesh] = None

DATA_AXES = ("pod", "data")     # batch shards over every present data-like axis
MODEL_AXIS = "model"


def set_mesh(mesh: Optional[Mesh]):
    """Install ``mesh`` as the process-global active mesh (None clears it)."""
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    """The active mesh, or None when running single-device."""
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Context manager: install ``mesh`` for the block, restore on exit."""
    prev = _MESH
    set_mesh(mesh)
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        set_mesh(prev)


def shard_map(f, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma`` off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def mesh_axes() -> frozenset[str]:
    """Axis names of the active mesh (empty frozenset when none)."""
    return frozenset(_MESH.axis_names) if _MESH is not None else frozenset()


def resolve(spec: P) -> P:
    """Drop logical axes that the active mesh does not have."""
    axes = mesh_axes()

    def keep(ax):
        if ax is None:
            return None
        if isinstance(ax, tuple):
            kept = tuple(a for a in ax if a in axes)
            return kept if kept else None
        return ax if ax in axes else None

    return P(*(keep(ax) for ax in spec))


def batch_spec(*trailing) -> P:
    """P(("pod","data"), *trailing) resolved against the mesh."""
    return P(DATA_AXES, *trailing)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint against the active mesh (no-op without one)."""
    if _MESH is None or _MESH.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, resolve(spec)))


def replicated(x: jax.Array) -> jax.Array:
    """Pin ``x`` fully replicated under the active mesh (no-op without one).

    The explicit cross-device exchange point: a data-sharded value constrained
    replicated lowers to one all-gather.  Scatter/segment update paths use it
    on their (ids, grads) inputs — GSPMD's cost model may otherwise leave
    scatter *updates* sharded on an axis the operand does not have, which
    applies each replica's partial update set and silently drops the rest
    (observed on jax 0.4.37 with a data-sharded batch updating a
    model-sharded table).  Replicated updates make every such op a local,
    update-order-preserving scatter over the operand's own shard, keeping
    the sharded table trajectory aligned with the single-device one to
    float rounding."""
    if _MESH is None or _MESH.empty:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, P()))


def named(spec: P) -> Optional[NamedSharding]:
    """NamedSharding of ``spec`` on the active mesh, or None without one."""
    if _MESH is None:
        return None
    return NamedSharding(_MESH, resolve(spec))


def tree_shardings(mesh: Mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree against ``mesh`` (the
    form ``jax.jit``'s in/out_shardings and ``jax.device_put`` consume)."""
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def active_mesh() -> Optional[Mesh]:
    """The installed mesh when it can actually shard (>1 device), else None —
    the guard executable sharded paths use to fall back to single-device."""
    if _MESH is None or _MESH.empty or _MESH.size <= 1:
        return None
    return _MESH


def data_shards() -> int:
    """Product of the data-parallel axis sizes of the active mesh."""
    if _MESH is None:
        return 1
    n = 1
    for a in DATA_AXES:
        if a in _MESH.axis_names:
            n *= _MESH.shape[a]
    return n


def model_shards() -> int:
    """Size of the model axis of the active mesh (1 when absent)."""
    if _MESH is None or MODEL_AXIS not in _MESH.axis_names:
        return 1
    return _MESH.shape[MODEL_AXIS]
