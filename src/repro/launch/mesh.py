"""Production mesh construction.

(16, 16) = one pod of 256 chips (data × model over ICI);
(2, 16, 16) = two pods (pod axis over DCN).  Defined as a function so that
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types: sharding is propagated by the
    compiler (jit shardings, ``shard_map`` specs), not carried in types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh over however many (fake) devices a test session has."""
    return make_mesh((data, model), ("data", "model"))


def chips(mesh) -> int:
    return mesh.devices.size


def make_serve_mesh(n_devices: int | None = None):
    """Flat 1-D serving mesh over the host's local devices, axis ``jobs``.

    The serving job axis (core/controller.py ``sharded_job_mega_fn``,
    serve/snn_serve.py) is embarrassingly parallel — no collectives inside
    a round, each device runs its job shard's while_loop independently —
    so the mesh is one axis wide and sized to whatever devices this host
    actually has (or ``n_devices``, e.g. under
    ``--xla_force_host_platform_device_count``).
    """
    n = n_devices or len(jax.devices())
    return make_mesh((n,), ("jobs",))
