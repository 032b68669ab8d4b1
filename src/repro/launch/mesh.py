"""Mesh construction and the chip peaks table.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
Every mesh in the repository is built by ``make_mesh``.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor. Axes are ``Auto``: the logical-axis rules
    (``dist/sharding.py``) place arrays with ``with_sharding_constraint``,
    which refers only to Auto mesh axes, while ``jax.make_mesh`` defaults
    to Explicit ones."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) over ("data", "model") = 256 chips.
    Multi-pod:   (2, 16, 16) over ("pod", "data", "model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def axis_sizes(mesh) -> dict:
    """{axis name: size} for a mesh (the {"data": 16, "model": 16} map)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_host_mesh(*, model_axis: int = 2):
    """("data", "model") mesh over whatever devices the host exposes.

    With XLA_FLAGS=--xla_force_host_platform_device_count=8 on the CPU
    this yields a (4, 2) mesh, small enough to compile quickly but
    multi-device along both logical directions so every sharding rule is
    exercised for real; on a four-chip TPU host it is (2, 2)."""
    n = jax.device_count()
    model_axis = max(1, min(model_axis, n))
    while n % model_axis:
        model_axis -= 1
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect). ``ici_bw`` is the
#: 50 GB/s per link the dry-run's collective term has always used.
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}

#: The chip the production meshes (and so the dry-run's roofline) target.
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: Optional[str] = None) -> Optional[dict]:
    """Peaks of ``device_kind`` (default: this process's first device), or
    None for a device not in ``CHIP_PEAKS`` — the CPU among them. Callers
    report no roofline figure then; there is no default chip."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    return CHIP_PEAKS.get(device_kind)
