"""Production meshes.

Single pod: 16×16 = 256 chips (v5e-256-like), axes (data, model).
Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model) — the ``pod`` axis
rides DCN; gradient all-reduce over it is the compressed axis
(parallel/compression.py).

Defined as functions (never module-level) so importing this module touches
no jax device state; the dry-run overrides the platform device count before
any jax import.
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for multi-device host tests (8 forced host devices)."""
    return make_auto_mesh(shape, axes)


def make_case_mesh(n_devices: int | None = None, axis: str = "case"):
    """1-D global mesh over the ensemble-case axis for campaign sharding.

    Ensemble time-history cases are embarrassingly parallel (no halo, no
    collective): one mesh axis over all (or the first ``n_devices``)
    devices is the whole story.  Each device then streams its own members'
    host-resident spring state through the StreamEngine.

    Under ``jax.distributed`` the default spans **every process's** devices
    — the multi-host campaign mesh.  The mesh is built directly over
    ``jax.devices()`` order (process-major: all of process 0's devices,
    then process 1's, …) rather than through ``jax.make_mesh``, whose
    topology-aware reordering could interleave processes; the campaign
    runner derives each process's *owned contiguous slice* of the case
    axis from exactly this order (``repro.campaign.runner.case_topology``).
    """
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"requested {n} devices, have {len(devices)}")
    import numpy as np

    return jax.sharding.Mesh(np.asarray(devices[:n]), (axis,))
