"""Production meshes (DESIGN.md §5).

Defined as functions, not module constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).

Topology: TPU v5e, 16x16 = 256 chips per pod; multi-pod = 2 pods = 512 chips.
Axes: ``data`` (in-pod DP / ZeRO), ``model`` (TP/EP/vocab rows), ``pod``
(cross-pod DP with compressed gradient all-reduce).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``: the repo places arrays
    with ``with_sharding_constraint``/``NamedSharding``, which only accept
    Auto axes (``make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The (16, 16) production mesh — or the (2, 16, 16) multi-pod variant."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = math.prod(shape)
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (see launch/dryrun.py)")
    return _mesh(shape, axes, devices[:ndev])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests / examples)."""
    ndev = data * model
    devices = jax.devices()[:ndev]
    return _mesh((data, model), ("data", "model"), devices)


def make_data_mesh(data: int = 0):
    """Pure data-parallel mesh; ``data=0`` takes every visible device.

    The forced-host-device recipe (laptops / CI) pairs this with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before the
    first jax import, which splits one CPU into N devices — real collectives
    and sharded buffers, shared silicon (correctness, not speedup).
    """
    n = data or len(jax.devices())
    return make_host_mesh(data=n, model=1)
