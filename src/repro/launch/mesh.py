"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses the inter-pod (DCI) links; gradient compression in
``repro.dist.compression`` targets exactly that axis.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import math

import jax


def _make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with ``Auto`` axes: everything downstream uses
    explicit NamedShardings and ``with_sharding_constraint``, which the
    default ``Explicit`` axes refuse."""
    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} "
            "(dry-run sets --xla_force_host_platform_device_count=512)"
        )
    return _make_mesh(shape, axes, devices)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    dp = max(1, n // model_parallel)
    return _make_mesh((dp, model_parallel), ("data", "model"), jax.devices()[: dp * model_parallel])
