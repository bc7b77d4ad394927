"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — device count is locked on first jax init;
callers that need placeholder host devices (dry-run, multi-device CI,
tensor-parallel CPU smoke runs) request them via
:func:`ensure_host_device_count` BEFORE first device use.
"""
from __future__ import annotations

import os

import jax


def ensure_host_device_count(n: int) -> bool:
    """Request ≥ ``n`` XLA host-platform devices for this process.

    Appends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    *preserving any flags already set*; an existing device-count flag is
    respected as-is (the caller pinned it deliberately).  The count is
    locked when JAX first initializes its backends, so call this before
    any device use.  Returns True when ≥ ``n`` devices are visible.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count=" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    return jax.device_count() >= n


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pipe: int = 1):
    """Tiny mesh for CPU smoke tests / examples (1 real device).

    ``pipe > 1`` appends a pipeline-stage axis; ``pipe == 1`` keeps the
    exact 2-axis mesh (and HLO) of the pre-pipeline engine."""
    if pipe > 1:
        return _make_mesh((data, model, pipe), ("data", "model", "pipe"))
    return _make_mesh((data, model), ("data", "model"))
