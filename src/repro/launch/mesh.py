"""Production mesh definition (multi-pod dry-run contract).

A FUNCTION, not a module constant, so importing this module never touches
jax device state.  Single pod: (data=16, model=16) = 256 chips; multi-pod:
(pod=2, data=16, model=16) = 512 chips.  TPU v5e constants for the roofline
live here too.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh whose axes are all Auto: sharding is steered by the
    ``with_sharding_constraint`` rules, not by explicit-axis typing."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e per-chip hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
HBM_BYTES = 16 * 1024 ** 3
