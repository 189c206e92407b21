"""Sparse matrix-vector products: stored BCSR vs matrix-free EBE.

The paper's Proposed Method 2 converts the memory-bandwidth-bound CRS SpMV
into on-the-fly element products (EBE, [8]) — more FLOPs, far less memory
traffic, no stored matrix.  TPU adaptation (DESIGN.md §8): the scatter-add
that CUDA does with L2 atomics becomes a *sorted segment-sum* over a
precomputed permutation (deterministic, TPU-idiomatic).

The jnp implementations here are the reference path; kernels/ebe_matvec
holds the Pallas kernel for the per-element contraction (the flop hotspot),
wired in through the same gather/scatter maps whenever the dispatch layer
(repro.fem.backend) resolves to it.  Contractions over the small tensor
axes are elementwise multiply-and-sum, never ``dot``: exact fp32 on every
backend (a TPU fp32 dot defaults to one bf16 pass).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fem import assembly, quadrature as quad
from repro.fem.assembly import block_entries


# ---------------------------------------------------------------------------
# BCSR 3×3 (stored-matrix) path
# ---------------------------------------------------------------------------


def bcsr_matvec(
    values: jnp.ndarray,  # [9*nnzb] BCSR 3×3 values (assembly.assemble_bcsr)
    rowids: np.ndarray,   # [nnzb] sorted
    col_idx: np.ndarray,  # [nnzb]
    x: jnp.ndarray,       # [N,3]
) -> jnp.ndarray:
    """y[i] = Σ_j A[i,j] x[j] with 3×3 blocks (gather + sorted scatter-add),
    one flat vector per component so nothing is laid out with a minor 3."""
    cols, rows = jnp.asarray(col_idx), jnp.asarray(rowids)
    xj = [x[:, b][cols] for b in range(3)]             # 3 × [nnzb]
    y = [
        jnp.zeros(x.shape[0], x.dtype).at[rows].add(
            sum(block_entries(values, 3 * a + b) * xj[b] for b in range(3)),
            indices_are_sorted=True)
        for a in range(3)
    ]
    return jnp.stack(y, axis=1)


# ---------------------------------------------------------------------------
# shared gather / scatter machinery (element index minor)
# ---------------------------------------------------------------------------
#
# Per-element quantities are laid out with the element index on the minor
# axis — nodal values ``[10, 3, E]``, strains and stresses ``[P, 6, E]`` —
# and the small tensor dimensions are leading axes.  A TPU tiles the two
# minor axes of an array to (8, 128): an ``[E, 10, 3]`` array pads ~70-fold
# in HBM, and the XLA programs that gather into or reshape through such
# layouts compile about ten times slower than this form.


def gather_nodal(u: jnp.ndarray, mesh) -> jnp.ndarray:
    """Nodal values per element ``[10, 3, E]`` from ``u [N,3]``."""
    idx = jnp.asarray(mesh.conn.T.reshape(-1))  # (node slot, element)
    E = mesh.n_elem
    return jnp.stack([u[:, a][idx].reshape(quad.NNODE, E) for a in range(3)], axis=1)


def scatter_nodal(f: jnp.ndarray, mesh) -> jnp.ndarray:
    """Σ of element contributions ``f [10, 3, E]`` per node → ``[N,3]``, by
    a sorted segment-sum per component (the atomic-add replacement:
    deterministic, TPU-idiomatic)."""
    perm, seg = jnp.asarray(mesh.node_perm), jnp.asarray(mesh.node_segids)
    return jnp.stack([
        jax.ops.segment_sum(f[:, a].reshape(-1)[perm], seg,
                            num_segments=mesh.n_nodes, indices_are_sorted=True)
        for a in range(3)
    ], axis=1)


# ---------------------------------------------------------------------------
# EBE (matrix-free) path — strain / stress / element matvec
# ---------------------------------------------------------------------------


def strain_lanes(u: jnp.ndarray, Jinv: jnp.ndarray) -> jnp.ndarray:
    """Voigt strain at Gauss points ``[P, 6, E]`` from ``u [10, 3, E]``.

    ε = sym(∇u); engineering shear (γ = 2ε_offdiag) to match B-matrices.
    """
    g = assembly.lane_major_gradients(Jinv)                     # [P,3,10,E]
    # H[p,i,j] = ∂u_i/∂x_j = Σ_n u[n,i] g[p,j,n]
    H = (u[None, :, :, None, :] * jnp.swapaxes(g, 1, 2)[:, :, None]).sum(axis=1)
    return jnp.stack([H[:, 0, 0], H[:, 1, 1], H[:, 2, 2],
                      H[:, 0, 1] + H[:, 1, 0],
                      H[:, 1, 2] + H[:, 2, 1],
                      H[:, 2, 0] + H[:, 0, 2]], axis=1)


def force_lanes(s: jnp.ndarray, Jinv: jnp.ndarray) -> jnp.ndarray:
    """``f [10, 3, E]`` = Σ_p B_pᵀ s_p for weighted Voigt stresses
    ``s [P, 6, E]``: f[n,i] = Σ_p Σ_j σ_ij(p) ∂N_n/∂x_j."""
    g = assembly.lane_major_gradients(Jinv)                     # [P,3,10,E]
    V = np.asarray(assembly.VOIGT)
    st = s[:, V]                                                # [P,3,3,E] σ_ij
    f = (st[:, :, :, None, :] * g[:, None]).sum(axis=(0, 2))    # [3,10,E]
    return jnp.swapaxes(f, 0, 1)


def ebe_element_lanes(
    uT: jnp.ndarray,     # [30,E] nodal displacements, row 3n+a
    D: jnp.ndarray,      # [E,P,6,6] tangent at Gauss points
    Jinv: jnp.ndarray,   # [E,3,3]
    wdet: jnp.ndarray,   # [E,P]
    coef_e: jnp.ndarray | None = None,  # [E] per-element scale (e.g. 1+2β_e/dt)
) -> jnp.ndarray:
    """K_e u_e without forming K_e: ε → Dε → Bᵀ, fused (the EBE product),
    in the element kernel's lane-major calling convention (→ ``[30,E]``)."""
    E, P = D.shape[:2]
    eps = strain_lanes(uT.reshape(quad.NNODE, 3, E), Jinv)      # [P,6,E]
    Dt = jnp.moveaxis(D, 0, -1)                                 # [P,6,6,E]
    sig = (Dt * eps[:, None]).sum(axis=2)                       # [P,6,E]
    w = wdet.T if coef_e is None else wdet.T * coef_e[None]
    return force_lanes(sig * w[:, None], Jinv).reshape(quad.NDOF, E)


def ebe_matvec(
    x: jnp.ndarray,  # [N,3]
    D: jnp.ndarray,
    mesh,
    coef_e: jnp.ndarray | None = None,
    element_kernel=None,
) -> jnp.ndarray:
    """Full matrix-free K·x (gather → element product → sorted scatter).

    ``element_kernel`` (lane-major: ``[30,E]`` in and out, the
    ``ebe_element_lanes`` convention) lets the Pallas kernel replace the jnp
    contraction.
    """
    E = mesh.n_elem
    uT = gather_nodal(x, mesh).reshape(quad.NDOF, E)
    kern = element_kernel or ebe_element_lanes
    fT = kern(uT, D, jnp.asarray(mesh.Jinv, x.dtype), jnp.asarray(mesh.wdet, x.dtype), coef_e)
    return scatter_nodal(fT.reshape(quad.NNODE, 3, E), mesh)


# ---------------------------------------------------------------------------
# per-evaluation-point path: strain in, stress out (the multispring interface)
# ---------------------------------------------------------------------------
#
# The multispring update works on evaluation points ``q = e*P + p``.  Moving
# between ``[P, …, E]`` element layouts and that point order interleaves P
# into the minor axis — a relayout whose XLA code compiles for ~100 s at
# 48,000 elements.  So strain and internal force are computed per point,
# from tables built once per mesh on the host.


class PointTables(NamedTuple):
    """Per-point gather/scatter maps and shape-function gradients, point
    index ``q = e*P + p`` on the minor axis."""

    nodes: jnp.ndarray    # [10, E*P] node of slot n of q's element
    grads: jnp.ndarray    # [3, 10, E*P] ∂N_n/∂x_j at q
    wdet: jnp.ndarray     # [E*P] quadrature weight × |J|
    perm: jnp.ndarray     # [10*E*P] argsort of nodes.ravel()
    segids: jnp.ndarray   # [10*E*P] sorted node ids
    n_nodes: int


def point_tables(mesh, dtype) -> PointTables:
    nodes = np.repeat(mesh.conn, quad.NPOINT, axis=0).T           # [10, E*P]
    g = quad.physical_gradients(np.asarray(mesh.Jinv))            # [E,P,10,3]
    grads = g.transpose(3, 2, 0, 1).reshape(3, quad.NNODE, -1)
    flat = nodes.reshape(-1)
    perm = np.argsort(flat, kind="stable")
    return PointTables(
        nodes=jnp.asarray(nodes, jnp.int32),
        grads=jnp.asarray(grads, dtype),
        wdet=jnp.asarray(np.asarray(mesh.wdet).reshape(-1), dtype),
        perm=jnp.asarray(perm, jnp.int32),
        segids=jnp.asarray(flat[perm], jnp.int32),
        n_nodes=mesh.n_nodes,
    )


def strain_at_points(u: jnp.ndarray, pts: PointTables) -> jnp.ndarray:
    """Total strain at all evaluation points ``[E*P, 6]`` (multispring
    input); engineering shear, as the B-matrices."""
    g = pts.grads.astype(u.dtype)
    uq = [u[:, a][pts.nodes] for a in range(3)]                   # 3 × [10,E*P]
    H = [[(uq[i] * g[j]).sum(axis=0) for j in range(3)] for i in range(3)]
    return jnp.stack([H[0][0], H[1][1], H[2][2],
                      H[0][1] + H[1][0], H[1][2] + H[2][1], H[2][0] + H[0][2]]).T


def internal_force(sigma_pts: jnp.ndarray, pts: PointTables) -> jnp.ndarray:
    """Assembled internal force q ``[N,3]`` from point stresses ``[E*P,6]``:
    q[node, i] = Σ_q Σ_j σ_ij(q) wdet(q) ∂N/∂x_j, sorted segment-sums."""
    g = pts.grads.astype(sigma_pts.dtype)
    s = sigma_pts.T * pts.wdet.astype(sigma_pts.dtype)[None]      # [6,E*P]
    V = assembly.VOIGT
    return jnp.stack([
        jax.ops.segment_sum(
            sum(s[V[a][j]][None] * g[j] for j in range(3)).reshape(-1)[pts.perm],
            pts.segids, num_segments=pts.n_nodes, indices_are_sorted=True)
        for a in range(3)
    ], axis=1)
