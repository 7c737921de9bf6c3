"""Mesh construction — the one place in the repo that builds a ``Mesh``.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the ``pod``
axis rides on DCN (broker-bridging analogue), ``data``/``model`` on ICI.

Every axis is ``AxisType.Auto``: the data plane relies on GSPMD
propagation, ``with_sharding_constraint`` and ``shard_map`` over a mesh
context, which jax's default Explicit axes reject.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2, pods: int = 0,
                   devices=None):
    """Small mesh over the first ``data*model`` (x ``pods``) devices —
    CPU host devices in tests and examples, chips on a TPU host.
    ``devices`` overrides ``jax.devices()``, e.g. with the devices of a
    described (not attached) TPU topology for ahead-of-time compiles."""
    if pods:
        return _make_mesh((pods, data, model), ("pod", "data", "model"),
                          devices)
    return _make_mesh((data, model), ("data", "model"), devices)
