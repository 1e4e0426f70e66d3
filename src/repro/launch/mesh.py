"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialisation; tests and benches see the default single device.

Production target: TPU v5e pods, 256 chips each (16 x 16), 2 pods for
the multi-pod proof.  Axes:
  pod   -- inter-pod data parallelism (DCN-connected)
  data  -- intra-pod data parallel / ZeRO / context parallel
  model -- tensor / expert parallel (ICI-connected)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n_axes: int) -> tuple:
    # the sharding rules constrain activations and let GSPMD propagate
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(devices: int | None = None):
    """Small mesh over whatever devices exist (CPU tests)."""
    n = devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))


# Hardware constants for the roofline (TPU v5e)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
