"""Conjugate-gradient solvers.

* :func:`pcg` — 3×3 block-Jacobi preconditioned CG (the paper's CRS-PCG).
* :func:`fcg` — flexible CG whose preconditioner is an *inner*, lower-
  precision, block-Jacobi-PCG solve — our adaptation of the paper's
  "adaptive conjugate gradient with mixed-precision multigrid-based
  preconditioner" [9] (EBE-IPCG).  The inner solve runs in fp32 while the
  outer iteration keeps the solution precision; flexible (Polak–Ribière) β
  tolerates the inexact preconditioner.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class CGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray
    relres: jnp.ndarray
    converged: jnp.ndarray | bool = True
    """``relres ≤ tol`` at loop exit.  False means the solve hit ``maxiter``
    still above tolerance — previously indistinguishable from success — or
    went non-finite (NaN compares False, so a diverged solve reports
    unconverged, which is what the health layer keys on)."""


def _vdot(a, b):
    return jnp.sum(a * b)


def _tiny(x: jnp.ndarray) -> float:
    """Dtype-aware denominator guard.

    The former hard-coded ``1e-300`` flushes to ``0.0`` in float32 — the
    dtype the EBE inner preconditioner solves in — so a zero residual there
    divided by exactly zero.  ``finfo.tiny`` (the smallest normal number)
    is representable in every float dtype and still orders of magnitude
    below any meaningful denominator.
    """
    return float(jnp.finfo(x.dtype).tiny)


def pcg(
    matvec: Callable[[jnp.ndarray], jnp.ndarray],
    b: jnp.ndarray,
    precond: Callable[[jnp.ndarray], jnp.ndarray],
    *,
    tol: float = 1e-8,
    maxiter: int = 3000,
    x0: jnp.ndarray | None = None,
) -> CGResult:
    """Standard PCG on ‖r‖/‖b‖ ≤ tol, jit/scan-safe (lax.while_loop)."""
    x = jnp.zeros_like(b) if x0 is None else x0
    eps = _tiny(b)
    r = b - matvec(x)
    z = precond(r)
    p = z
    rz = _vdot(r, z)
    bnorm = jnp.sqrt(_vdot(b, b)) + eps

    def cond(state):
        _, r, *_, it = state
        return (jnp.sqrt(_vdot(r, r)) / bnorm > tol) & (it < maxiter)

    def body(state):
        x, r, p, rz, it = state
        Ap = matvec(p)
        alpha = rz / (_vdot(p, Ap) + eps)
        x = x + alpha * p
        r = r - alpha * Ap
        z = precond(r)
        rz_new = _vdot(r, z)
        beta = rz_new / (rz + eps)
        p = z + beta * p
        return (x, r, p, rz_new, it + 1)

    x, r, p, rz, it = jax.lax.while_loop(cond, body, (x, r, p, rz, jnp.zeros((), jnp.int32)))
    relres = jnp.sqrt(_vdot(r, r)) / bnorm
    return CGResult(x=x, iters=it, relres=relres, converged=relres <= tol)


def fcg(
    matvec: Callable[[jnp.ndarray], jnp.ndarray],
    b: jnp.ndarray,
    inner_precond: Callable[[jnp.ndarray], jnp.ndarray],
    *,
    tol: float = 1e-8,
    maxiter: int = 3000,
    x0: jnp.ndarray | None = None,
) -> CGResult:
    """Flexible CG: β via Polak–Ribière so an inexact (iterative, mixed-
    precision) preconditioner is admissible."""
    x = jnp.zeros_like(b) if x0 is None else x0
    eps = _tiny(b)
    r = b - matvec(x)
    z = inner_precond(r)
    p = z
    bnorm = jnp.sqrt(_vdot(b, b)) + eps

    def cond(state):
        _, r, *_rest, it = state
        return (jnp.sqrt(_vdot(r, r)) / bnorm > tol) & (it < maxiter)

    def body(state):
        x, r, p, z, it = state
        Ap = matvec(p)
        alpha = _vdot(r, z) / (_vdot(p, Ap) + eps)
        x = x + alpha * p
        r_new = r - alpha * Ap
        z_new = inner_precond(r_new)
        # Polak–Ribière (flexible): β = z_new·(r_new − r) / z·r
        beta = _vdot(z_new, r_new - r) / (_vdot(z, r) + eps)
        p = z_new + beta * p
        return (x, r_new, p, z_new, it + 1)

    x, r, p, z, it = jax.lax.while_loop(cond, body, (x, r, p, z, jnp.zeros((), jnp.int32)))
    relres = jnp.sqrt(_vdot(r, r)) / bnorm
    return CGResult(x=x, iters=it, relres=relres, converged=relres <= tol)


def make_inner_pcg_preconditioner(
    matvec32: Callable[[jnp.ndarray], jnp.ndarray],
    block_jacobi32: Callable[[jnp.ndarray], jnp.ndarray],
    *,
    inner_iters: int = 8,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Fixed-iteration fp32 block-Jacobi PCG as a preconditioner M⁻¹r.

    The paper's multigrid preconditioner [9] uses a cheap low-precision
    inner solve on (a coarsened version of) the same operator; with the
    paper's mesh unavailable we keep the same-level variant: ``inner_iters``
    fp32 PCG sweeps.  Fixed iteration count keeps it (almost) linear;
    flexible outer CG absorbs the rest.
    """

    def apply(r: jnp.ndarray) -> jnp.ndarray:
        r32 = r.astype(jnp.float32)
        eps = _tiny(r32)
        x = jnp.zeros_like(r32)
        rr = r32
        z = block_jacobi32(rr)
        p = z
        rz = _vdot(rr, z)

        def body(i, state):
            x, rr, p, rz = state
            Ap = matvec32(p)
            alpha = rz / (_vdot(p, Ap) + eps)
            x = x + alpha * p
            rr = rr - alpha * Ap
            z = block_jacobi32(rr)
            rz_new = _vdot(rr, z)
            beta = rz_new / (rz + eps)
            p = z + beta * p
            return (x, rr, p, rz_new)

        x, *_ = jax.lax.fori_loop(0, inner_iters, body, (x, rr, p, rz))
        return x.astype(r.dtype)

    return apply


def flat(v: jnp.ndarray) -> jnp.ndarray:
    """Nodal ``[N,3]`` → the solvers' flat vector, component-major (all x,
    then y, then z).  The interleaved ``v.reshape(-1)`` is a TPU relayout
    whose XLA code takes ~20 s to compile per occurrence at 50K elements;
    this transpose compiles in about one."""
    return v.T.reshape(-1)


def unflat(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`flat`."""
    return x.reshape(3, -1).T


def block_jacobi_apply(Minv: jnp.ndarray) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Inverted 3×3 diagonal blocks, lane-major ``[9, N]`` (row-major
    entries, ``assembly.inv3``) → preconditioner on :func:`flat` vectors."""

    def apply(r: jnp.ndarray) -> jnp.ndarray:
        M = Minv.astype(r.dtype)
        r3 = unflat(r).T  # [3, N]: transposes that cancel, no data movement
        return flat(jnp.stack([sum(M[3 * a + b] * r3[b] for b in range(3))
                               for a in range(3)]).T)

    return apply
