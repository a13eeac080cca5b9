"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never module-level state) so that
importing this module does not touch jax device initialization — the
dry-run must set XLA_FLAGS before any device query.

All mesh construction goes through :mod:`repro.compat`, the one module
that touches the mesh API.
"""

from __future__ import annotations

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    The "pod" axis crosses DCN; "data"/"model" stay on ICI.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(model: int | None = None, devices=None):
    """(data, model) mesh over ``devices`` (default: every local device).

    A device subset (``jax.devices()[:n]``) lets one process measure the
    same job at several device counts — the strong-scaling table — without
    a child process per count.
    """
    devices = list(jax.devices() if devices is None else devices)
    model = model or 1
    return compat.make_mesh(
        (len(devices) // model, model), ("data", "model"), devices=devices
    )


def devices_per_pod(mesh) -> int | None:
    """Device count inside one pod (None when single-pod => no DCN)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "pod" not in sizes or sizes["pod"] == 1:
        return None
    total = 1
    for s in mesh.devices.shape:
        total *= s
    return total // sizes["pod"]
