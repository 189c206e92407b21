"""Least work of one matrix-free EBE product y = Σ_e c_e K_e(D) x, one
case, at the configuration's fp32 (4-byte words).

Bytes: the tangent D at 21 words per Gauss point (it is symmetric), and the
nodal vectors x read once and y written once (3 words per node each).
Element geometry, quadrature weights and the per-element scale are not
counted: an implementation may fold them into D.  Flops, per point: the
displacement gradient H = Σ_n x_n ⊗ ∇N_n (90 multiply-adds), σ = Dε (36),
f_n += σ ∇N_n (90); forming the gradients is not counted.  These are lower
bounds of the operation, whatever the kernel's layout, packing or fusion."""
from __future__ import annotations

WORD = 4
D_WORDS = 21
MACS_PER_POINT = 90 + 36 + 90


def count(n_elem: int, n_nodes: int, npoint: int = 4) -> tuple[float, float]:
    """(flops, bytes) of one product for one case."""
    flops = 2.0 * MACS_PER_POINT * npoint * n_elem
    nbytes = WORD * (D_WORDS * npoint * n_elem + 2 * 3 * n_nodes)
    return flops, float(nbytes)
