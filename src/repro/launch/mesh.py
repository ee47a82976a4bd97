"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import os

import jax


def force_host_devices() -> None:
    """512 host devices for the production mesh.  The dry-run entry
    points call it in ``main`` before the first backend call, so importing
    them changes nothing."""
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 v5e chips) or 2x16x16 (two pods, 512 chips).

    Axes:
      pod    pure data parallelism across pods (gradient all-reduce
             crosses the inter-pod DCN/ICI boundary — the multi-pod
             dry-run proves this lowers)
      data   DP for training / batch sharding for decode; also the
             ZeRO-style second weight-sharding axis
      model  tensor/expert parallelism
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)
