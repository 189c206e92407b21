"""Jitted public entry for the EBE element kernel.

``element_kernel(...)`` matches the ``fem.spmv`` ``element_kernel`` calling
convention (lane-major ``[30,E]`` in and out, as ``spmv.ebe_element_lanes``)
so it can be dropped straight into ``spmv.ebe_matvec`` /
``methods.FemOperators(element_kernel=...)``.
"""
from __future__ import annotations

import jax

from repro.kernels.ebe_matvec.ebe_matvec import (
    ebe_element_lanes_pallas,
    ebe_element_matvec_pallas,
)
from repro.kernels.ebe_matvec.ref import ebe_element_matvec_ref


def element_kernel(uT, D, Jinv, wdet, coef=None, *, tile_e: int = 512, interpret: bool | None = None):
    """Pallas EBE element product; interpret defaults to True off-TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return ebe_element_lanes_pallas(
        uT, D, Jinv, wdet, coef, tile_e=tile_e, interpret=interpret
    )


__all__ = ["element_kernel", "ebe_element_lanes_pallas", "ebe_element_matvec_pallas",
           "ebe_element_matvec_ref"]
