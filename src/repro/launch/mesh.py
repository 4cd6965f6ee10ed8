"""Production mesh construction.

Defined as a FUNCTION (not module-level state) so importing this module never
touches jax device initialization — critical because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init,
while tests must see their own devices.

All mesh construction routes through ``repro.compat.make_mesh`` so the
``axis_types`` / ``AxisType`` API drift is absorbed in one place.
"""

from __future__ import annotations

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips) mesh.

    Axes: ``pod`` (DCN between pods), ``data`` (APSS rows), ``model``
    (APSS dimensions).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
