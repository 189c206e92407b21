"""Element stiffness, BCSR 3×3 assembly, block-Jacobi — the CRS-side path.

Everything is jnp and jit-friendly; the mesh supplies static numpy index
maps.  The EBE (matrix-free) counterparts live in spmv.py; both paths share
the same on-the-fly gradients from the constant element Jacobians
(quadrature.GRADN_REF is a trace-time constant).

Layout: the stiffness is built and stored **lane-major** — every per-element
or per-block quantity is an array whose minor axis is the element (or BCSR
block) index, and the small 3×3 / 6×6 / 30×30 dimensions are Python-level
lists or unrolled sums (a leading axis of 9 is not safe: XLA may lay it
out minor).  A TPU tiles the two minor axes of an array to (8, 128);
an ``[E, 30, 30]`` element stiffness or ``[nnzb, 3, 3]`` BCSR value array
would pad 4- to 60-fold in HBM, which at 80K elements is more than a
16 GB chip holds.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.fem import quadrature as quad

# Voigt row of the symmetric tensor index pair (i, j) (engineering shear)
VOIGT = ((0, 3, 5), (3, 1, 4), (5, 4, 2))


def lane_major_gradients(Jinv: jnp.ndarray) -> jnp.ndarray:
    """∇_x N at Gauss points, element index minor: ``[P,3,10,E]``
    (``g[p, j, n, e] = Σ_k GRADN_REF[p,n,k] J⁻¹[e,k,j]``, elementwise)."""
    E = Jinv.shape[0]
    Jt = Jinv.reshape(E, 9).T  # [9,E], row k*3+j
    gref = quad.GRADN_REF
    return sum(
        jnp.asarray(gref[:, None, :, k, None], Jinv.dtype)
        * jnp.stack([Jt[3 * k + j] for j in range(3)])[None, :, None, :]
        for k in range(3)
    )


def element_blocks(D: jnp.ndarray, Jinv: jnp.ndarray, wdet: jnp.ndarray, *,
                   diagonal: bool = False) -> list:
    """Element stiffness Kₑ = Σ_p wdet_p Bₚᵀ Dₚ Bₚ (paper Eq. 2) as 3×3
    node-pair blocks: a list of the 9 block entries (row-major ``3a+b``),
    each ``[10, 10, E]`` — entry (a, b) of the block coupling node ``n``'s
    dof ``a`` to node ``m``'s dof ``b`` — or, with ``diagonal``, only the
    blocks n = m, each ``[10, E]`` (the EBE block-Jacobi preconditioner).

    With Voigt rows ``V``, block (n, m) is
    ``K[a,b] = Σ_p w_p Σ_ij g_i(n) g_j(m) D[V(a,i), V(b,j)]``."""
    E, P = D.shape[:2]
    Dt = D.reshape(E, P * 36).T                  # [P*36,E]
    w = wdet.astype(D.dtype).T                   # [P,E]
    g = lane_major_gradients(Jinv.astype(D.dtype))
    pair = (lambda x, y: x * y) if diagonal else (lambda x, y: x[:, None] * y[None])
    K = [0.0] * 9
    for p in range(P):
        gg = [[pair(g[p, i], g[p, j]) for j in range(3)] for i in range(3)]
        for a in range(3):
            for b in range(3):
                s = sum(gg[i][j] * Dt[36 * p + 6 * VOIGT[a][i] + VOIGT[b][j]]
                        for i in range(3) for j in range(3))
                K[3 * a + b] = K[3 * a + b] + w[p] * s
    return K


def element_stiffness(D: jnp.ndarray, Jinv: jnp.ndarray, wdet: jnp.ndarray) -> jnp.ndarray:
    """Dense Kₑ ``[E,30,30]`` (dof ``3n+a``) — for small checks only."""
    Kb = jnp.stack(element_blocks(D, Jinv, wdet)).reshape(3, 3, 10, 10, -1)
    return Kb.transpose(4, 2, 0, 3, 1).reshape(-1, quad.NDOF, quad.NDOF)


def assemble_bcsr(blocks: list, entry_map: np.ndarray, nnzb: int) -> jnp.ndarray:
    """Scatter :func:`element_blocks` into BCSR 3×3 values ``[9 * nnzb]``:
    block entry ``k`` of every block, then entry ``k + 1`` — one flat
    vector, so no 2-D layout can pad it.

    This is the paper's ``UpdateCRS`` — executed every time step because the
    multi-spring D changes, and the cost Proposed Method 2 eliminates.
    """
    idx = jnp.asarray(entry_map.transpose(1, 2, 0).reshape(-1))  # (n, m, e)
    return jnp.concatenate([
        jnp.zeros(nnzb, Kk.dtype).at[idx].add(Kk.reshape(-1)) for Kk in blocks
    ])


def block_entries(values: jnp.ndarray, k: int, slots=None) -> jnp.ndarray:
    """Entry ``k`` (row-major in the 3×3 block) of every BCSR block, or of
    the blocks at ``slots``."""
    nnzb = values.shape[0] // 9
    v = values[k * nnzb:(k + 1) * nnzb]
    return v if slots is None else v[jnp.asarray(slots)]


def add_diag(values: jnp.ndarray, diag_slots: np.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """Add per-node 3-vector ``d [N,3]`` (or ``[N,1]``) onto the diagonal
    blocks."""
    nnzb = values.shape[0] // 9
    idx = np.concatenate([k * nnzb + diag_slots for k in (0, 4, 8)])
    d = jnp.broadcast_to(d, (len(diag_slots), 3)).astype(values.dtype)
    return values.at[jnp.asarray(idx)].add(d.T.reshape(-1))


def inv3(m) -> jnp.ndarray:
    """Inverse of 3×3 matrices given as their 9 row-major entries (each an
    array of the batch shape) → ``[9, …]``, by cofactors: elementwise, so
    the batch stays on the minor axis.  Each matrix is scaled by its
    largest diagonal entry first — the cubic determinant of a stiffness
    block (entries ~1e12) overflows fp32."""
    scale = jnp.maximum(jnp.maximum(jnp.abs(m[0]), jnp.abs(m[4])), jnp.abs(m[8]))
    a, b, c, d, e, f, g, h, i = (x / scale for x in m)
    A, B, C = e * i - f * h, f * g - d * i, d * h - e * g
    det = a * A + b * B + c * C
    inv = jnp.stack([A, c * h - b * i, b * f - c * e,
                     B, a * i - c * g, c * d - a * f,
                     C, b * g - a * h, a * e - b * d])
    return inv / (det * scale)


def block_jacobi_inverse(values: jnp.ndarray, diag_slots: np.ndarray) -> jnp.ndarray:
    """Inverted 3×3 diagonal blocks, lane-major ``[9, N]`` (the paper's
    preconditioner; ``solver.block_jacobi_apply``)."""
    diag = [block_entries(values, k, diag_slots) + (1e-30 if k in (0, 4, 8) else 0.0)
            for k in range(9)]
    return inv3(diag)


def dense_assemble(K_e: jnp.ndarray, elem_dofs: np.ndarray, ndof: int) -> jnp.ndarray:
    """Dense assembly for small verification problems only."""
    A = jnp.zeros((ndof, ndof), K_e.dtype)
    idx = jnp.asarray(elem_dofs)  # [E,30]
    return A.at[idx[:, :, None], idx[:, None, :]].add(K_e)
