from repro.kernels.ebe_matvec.ops import (  # noqa: F401
    ebe_element_lanes_pallas,
    ebe_element_matvec_pallas,
    ebe_element_matvec_ref,
    element_kernel,
)
