"""Pure-jnp oracle for the EBE element product (no Pallas).

Identical math to fem/spmv.ebe_element_lanes (element-major here), restated here so the kernel
package is self-contained: f_e = Σ_p wdet_p·coef_e · B_pᵀ D_p B_p u_e with
B built on the fly from the constant element Jacobian.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.fem import quadrature as quad


def ebe_element_matvec_ref(
    u_e: jnp.ndarray,    # [E,10,3]
    D: jnp.ndarray,      # [E,P,6,6]
    Jinv: jnp.ndarray,   # [E,3,3]
    wdet: jnp.ndarray,   # [E,P]
    coef: jnp.ndarray | None = None,  # [E]
) -> jnp.ndarray:        # [E,10,3]
    gref = jnp.asarray(quad.GRADN_REF, u_e.dtype)          # [P,10,3]
    g = jnp.einsum("pnk,ekj->epnj", gref, Jinv)            # ∇_x N
    H = jnp.einsum("epnj,eni->epij", g, u_e)               # ∂u_i/∂x_j
    eps = jnp.stack(
        [
            H[..., 0, 0],
            H[..., 1, 1],
            H[..., 2, 2],
            H[..., 0, 1] + H[..., 1, 0],
            H[..., 1, 2] + H[..., 2, 1],
            H[..., 2, 0] + H[..., 0, 2],
        ],
        axis=-1,
    )                                                      # [E,P,6]
    sig = jnp.einsum("epab,epb->epa", D, eps)
    w = wdet if coef is None else wdet * coef[:, None]
    s = sig * w[..., None]
    sxx, syy, szz, sxy, syz, szx = (s[..., k] for k in range(6))
    gx, gy, gz = g[..., 0], g[..., 1], g[..., 2]
    fx = jnp.einsum("epn,ep->en", gx, sxx) + jnp.einsum("epn,ep->en", gy, sxy) + jnp.einsum("epn,ep->en", gz, szx)
    fy = jnp.einsum("epn,ep->en", gx, sxy) + jnp.einsum("epn,ep->en", gy, syy) + jnp.einsum("epn,ep->en", gz, syz)
    fz = jnp.einsum("epn,ep->en", gx, szx) + jnp.einsum("epn,ep->en", gy, syz) + jnp.einsum("epn,ep->en", gz, szz)
    return jnp.stack([fx, fy, fz], axis=-1)
