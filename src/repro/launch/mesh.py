"""Mesh construction — device-count-derived, not hardcoded.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; everything else sees the real device count).

The old ``make_production_mesh``/``make_host_mesh`` pair hardcoded
``(16, 16)`` / ``(2, 16, 16)`` shapes and crashed on any host without
exactly 256/512 devices.  Shapes are now derived from ``jax.device_count()``
— the largest ``model`` axis that divides the device count (capped by
``model_cap``, typically the model's kv-head count so tensor parallelism
never degrades to replication), with ``data`` taking the rest.  The
dry-run's forced-512 topology stays reachable through the explicit
``shape=`` override (``MeshPlan.mesh_shape``).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def derive_mesh_shape(
    n_devices: Optional[int] = None,
    *,
    model_cap: Optional[int] = None,
    multi_pod: bool = False,
) -> Tuple[int, ...]:
    """Largest ``model`` axis dividing the device count (capped by
    ``model_cap``), ``data`` = the rest; ``multi_pod`` splits a leading pod
    axis of 2 when the count allows it."""
    n = jax.device_count() if n_devices is None else n_devices
    assert n >= 1
    pod = 1
    if multi_pod and n % 2 == 0:
        pod = 2
        n //= pod
    cap = n if model_cap is None else max(1, min(model_cap, n))
    model = max(d for d in range(1, cap + 1) if n % d == 0)
    data = n // model
    return (pod, data, model) if multi_pod else (data, model)


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the sharding code places
    arrays with ``with_sharding_constraint`` and lets GSPMD propagate,
    which ``Explicit`` axes (the newer ``make_mesh`` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(
    *,
    multi_pod: bool = False,
    shape: Optional[Sequence[int]] = None,
    model_cap: int = 16,
) -> Mesh:
    """Full-mesh factory for the dry-run / training path.

    ``shape=None`` derives the shape from the live device count; the
    dry-run passes its forced-512 topology (``MeshPlan.mesh_shape``)
    explicitly.  ``model_cap`` defaults to the historical 16-way model
    axis (an uncapped derivation would put EVERY device on ``model`` —
    wider than any head count, so the divisibility guard would silently
    replicate everything); pass the model's head count for a tighter fit.
    """
    if shape is None:
        shape = derive_mesh_shape(model_cap=model_cap, multi_pod=multi_pod)
    shape = tuple(shape)
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    assert len(shape) == len(axes), (shape, axes)
    return _auto_mesh(shape, axes)


def make_serving_mesh(
    shape: Optional[Sequence[int]] = None,
    *,
    n_kv_heads: Optional[int] = None,
) -> Mesh:
    """``(data, model)`` mesh for the serving engine.

    ``shape=None`` derives from ``jax.device_count()`` with the ``model``
    axis capped by ``n_kv_heads`` (kv-head tensor parallelism without
    replication); a single-device host yields the degenerate ``(1, 1)``
    mesh, so the engine path is mesh-agnostic.
    """
    if shape is None:
        shape = derive_mesh_shape(model_cap=n_kv_heads)
    shape = tuple(shape)
    assert len(shape) == 2, f"serving mesh is (data, model), got {shape}"
    return _auto_mesh(shape, ("data", "model"))


def parse_mesh_arg(arg: str) -> Tuple[int, int]:
    """``--mesh data,model`` flag value -> ``(data, model)`` shape."""
    parts = [p.strip() for p in arg.split(",")]
    if len(parts) != 2:
        raise ValueError(f"--mesh expects 'data,model' (e.g. '4,2'), got {arg!r}")
    return int(parts[0]), int(parts[1])
