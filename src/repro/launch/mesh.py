"""Production mesh definitions.

Functions (not module-level constants) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips of a
v5e pod.  Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod" axis
is an outer data-parallel/FSDP axis whose collectives cross the DCN/ICI
boundary between pods.

Every mesh of the repo is built by :func:`make_mesh`, whose axes are all
``AxisType.Auto``: placement follows the ``with_sharding_constraint`` /
``device_put`` shardings that ``repro.sharding`` derives, and the compiler
propagates the rest.  (``jax.make_mesh`` alone builds Explicit axes, under
which those constraints name axes the tracing context does not have.)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — smoke tests."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def make_client_mesh(data: int | None = None):
    """1-D data-parallel mesh for the federation path.

    The client-stacked data plane (exchange gate, AE pretrain, FL rounds)
    shards only its leading CLIENTS axis, so a pure ("data",) mesh is the
    natural layout; ``data`` defaults to every visible device (on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` before import to
    get K virtual devices).
    """
    d = len(jax.devices()) if data is None else data
    return make_mesh((d,), ("data",))
