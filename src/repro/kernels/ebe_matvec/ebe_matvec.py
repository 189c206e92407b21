"""Pallas TPU kernel for the EBE element product (Proposed Method 2 hotspot).

TPU adaptation of the paper's CUDA EBE kernel (DESIGN.md §8):

* the **element index lives on the 128-lane axis** — every per-element
  scalar quantity (a Jacobian entry, one strain component at one Gauss
  point) is a `[TILE_E]`-wide vector register;
* the small tensor dimensions (10 nodes × 3 coords × 6 Voigt × P Gauss
  points) are **fully unrolled at trace time**; the reference shape-function
  gradients are compile-time constants folded into the FMA stream;
* no stored B or K_e — only `J⁻¹` (9 lanes-wide vectors), `wdet` and the
  constitutive `D` stream through VMEM, which is the entire point of EBE:
  trade FLOPs for memory traffic and capacity.

Data layout is struct-of-arrays with E innermost (``[k, E]``) so each block
is a ``[k, TILE_E]`` VMEM tile with E on lanes; the nodal operands arrive
that way from ``fem.spmv``'s lane-major gather.

VMEM budget per block (TILE_E=512, fp32):
  u 30·512·4 = 60 KB, Jinv 9·512·4 = 18 KB, D 4·36·512·4 = 288 KB,
  wdet 4·512·4 = 8 KB, out 60 KB, intermediates ≲ 200 KB → ≪ 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.fem import quadrature as quad

NPOINT = quad.NPOINT
NNODE = quad.NNODE

# static reference gradients: python floats, folded into the kernel
_GREF = [[[float(quad.GRADN_REF[p, n, k]) for k in range(3)] for n in range(NNODE)] for p in range(NPOINT)]

_VOIGT_PAIRS = ((0, 0), (1, 1), (2, 2), (0, 1), (1, 2), (2, 0))


def _ebe_kernel(u_ref, jinv_ref, wdet_ref, d_ref, coef_ref, out_ref):
    """One TILE_E block. Refs (leading dim = small, lanes = elements):

    u    [30, T]   nodal displacements (node-major: n0x n0y n0z n1x …)
    jinv [9,  T]   J⁻¹ row-major
    wdet [P,  T]   quadrature weight × |J|
    d    [P*36, T] tangent, Voigt row-major per point
    coef [1,  T]   per-element scale (1 + 2β_e/dt)
    out  [30, T]
    """
    u = u_ref[...]
    ji = jinv_ref[...]
    wd = wdet_ref[...]
    dd = d_ref[...]
    cf = coef_ref[0]

    jinv = [[ji[3 * r + c] for c in range(3)] for r in range(3)]  # [3][3] of [T]
    un = [[u[3 * n + i] for i in range(3)] for n in range(NNODE)]  # [10][3] of [T]

    f = [[jnp.zeros_like(u[0]) for _ in range(3)] for _ in range(NNODE)]
    for p in range(NPOINT):
        # physical gradients g[n][j] = Σ_k GREF[p][n][k] · J⁻¹[k][j]
        g = [
            [
                sum(_GREF[p][n][k] * jinv[k][j] for k in range(3) if _GREF[p][n][k] != 0.0)
                for j in range(3)
            ]
            for n in range(NNODE)
        ]
        # displacement gradient H[i][j] = Σ_n u[n][i] g[n][j]
        H = [
            [sum(un[n][i] * g[n][j] for n in range(NNODE)) for j in range(3)]
            for i in range(3)
        ]
        eps = [
            H[0][0],
            H[1][1],
            H[2][2],
            H[0][1] + H[1][0],
            H[1][2] + H[2][1],
            H[2][0] + H[0][2],
        ]
        # σ = D ε  (Voigt 6×6, row-major slab of d)
        sig = [
            sum(dd[36 * p + 6 * a + b] * eps[b] for b in range(6)) for a in range(6)
        ]
        w = wd[p] * cf
        sw = [sig[a] * w for a in range(6)]
        # tensor form for the Bᵀσ contraction
        st = [[sw[0], sw[3], sw[5]], [sw[3], sw[1], sw[4]], [sw[5], sw[4], sw[2]]]
        for n in range(NNODE):
            for i in range(3):
                f[n][i] = f[n][i] + sum(st[i][j] * g[n][j] for j in range(3))

    out_ref[...] = jnp.stack([f[n][i] for n in range(NNODE) for i in range(3)])


@functools.partial(jax.jit, static_argnames=("tile_e", "interpret"))
def ebe_element_lanes_pallas(
    uT: jnp.ndarray,     # [30,E] nodal displacements, row 3n+a
    D: jnp.ndarray,      # [E,P,6,6]
    Jinv: jnp.ndarray,   # [E,3,3]
    wdet: jnp.ndarray,   # [E,P]
    coef: jnp.ndarray | None = None,  # [E]
    *,
    tile_e: int = 512,
    interpret: bool = True,  # CPU container: interpret; TPU: False
) -> jnp.ndarray:
    """The element product in the lane-major convention of
    ``fem.spmv.ebe_element_lanes``: ``[30,E]`` in, ``[30,E]`` out.  The
    last block of an ``E`` that ``tile_e`` does not divide is ragged (its
    out-of-range lanes are never written back)."""
    E = uT.shape[1]
    dt = uT.dtype
    if coef is None:
        coef = jnp.ones((E,), dt)
    jT = Jinv.astype(dt).reshape(E, 9).T                 # [9,E]
    wT = wdet.astype(dt).T                               # [P,E]
    dT = D.astype(dt).reshape(E, NPOINT * 36).T          # [P*36,E]
    cT = coef.astype(dt)[None, :]                        # [1,E]
    spec = lambda rows: pl.BlockSpec((rows, tile_e), lambda i: (0, i))
    return pl.pallas_call(
        _ebe_kernel,
        grid=(pl.cdiv(E, tile_e),),
        in_specs=[spec(30), spec(9), spec(NPOINT), spec(NPOINT * 36), spec(1)],
        out_specs=spec(30),
        out_shape=jax.ShapeDtypeStruct((30, E), dt),
        interpret=interpret,
    )(uT, jT, wT, dT, cT)


def ebe_element_matvec_pallas(
    u_e: jnp.ndarray,    # [E,10,3]
    D: jnp.ndarray,      # [E,P,6,6]
    Jinv: jnp.ndarray,   # [E,3,3]
    wdet: jnp.ndarray,   # [E,P]
    coef: jnp.ndarray | None = None,  # [E]
    *,
    tile_e: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Element-major form ``[E,10,3]`` → ``[E,10,3]`` (tests, benchmarks)."""
    E = u_e.shape[0]
    fT = ebe_element_lanes_pallas(u_e.reshape(E, 30).T, D, Jinv, wdet, coef,
                                  tile_e=tile_e, interpret=interpret)
    return fT.T.reshape(E, NNODE, 3)
