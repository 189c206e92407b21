"""Paper Table 2: per-time-step breakdown — solver / CRS update / multispring.

Phases are timed by running each jitted piece standalone at the same state
(the paper instruments the same three phases).  The transfer column is
modeled from the pipeline model on this container (no device link).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.fem import backend as fem_backend, meshgen, methods, quadrature as quad, solver, spmv


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def main(n: int = 3, nspring: int = 12):
    mesh = meshgen.generate(n, n, n, pad_elems_to=8)
    cfg = methods.SeismicConfig(dt=0.01, tol=1e-6, maxiter=400, npart=4, nspring=nspring)
    ops = fem_backend.make_operators(mesh, cfg)
    carry = methods.initial_carry(ops)
    nm, springs, D, alpha, beta_e = carry
    b = jax.random.normal(jax.random.key(0), (mesh.ndof,), cfg.dtype)

    # phase: CRS update (assembly)
    crs_update = jax.jit(lambda D: ops.crs_update(D, beta_e, alpha))
    t_crs = _time(crs_update, D)
    valA, valCk, Minv = crs_update(D)

    # phase: CRS solver
    pcg = jax.jit(lambda b: solver.pcg(ops.crs_matvec(valA), b,
                                       solver.block_jacobi_apply(Minv), tol=cfg.tol,
                                       maxiter=cfg.maxiter).x)
    t_solve_crs = _time(pcg, b)

    # phase: EBE solver (matrix-free + fp32 inner preconditioner)
    mvA = ops.ebe_matvec_A(D, beta_e, alpha)
    Minv_e = ops.ebe_diag_inverse(D, beta_e, alpha)
    inner = solver.make_inner_pcg_preconditioner(
        mvA, solver.block_jacobi_apply(Minv_e.astype(jnp.float32)), inner_iters=cfg.inner_iters
    )
    fcg = jax.jit(lambda b: solver.fcg(mvA, b, inner, tol=cfg.tol, maxiter=cfg.maxiter).x)
    t_solve_ebe = _time(fcg, b)

    # phase: multispring (resident vs streamed)
    eps = spmv.strain_at_points(jax.random.normal(jax.random.key(1), (mesh.n_nodes, 3), cfg.dtype) * 1e-4, ops.points)
    ms_res = jax.jit(lambda e, s: ops.multispring_all(e, s))
    t_ms = _time(ms_res, eps, springs)

    print(f"{'phase':28s} {'s/step':>10s}")
    print(f"{'CRS update (UpdateCRS)':28s} {t_crs:10.4f}")
    print(f"{'solver CRS-PCG':28s} {t_solve_crs:10.4f}")
    print(f"{'solver EBE-IPCG':28s} {t_solve_ebe:10.4f}")
    print(f"{'multispring (compute)':28s} {t_ms:10.4f}")
    print(f"\nEBE eliminates the CRS-update phase entirely "
          f"({t_crs:.4f}s/step at this scale) — the paper's Prop.2 structural win.")
    return dict(crs_update=t_crs, solver_crs=t_solve_crs, solver_ebe=t_solve_ebe, multispring=t_ms)


if __name__ == "__main__":
    main()
