"""Multi-spring constitutive model (Iai 1993) with modified Ramberg–Osgood
backbone + Masing hysteresis — the memory-capacity-bound part of the paper.

Per material evaluation point, ``NSPRING`` 1-D nonlinear springs in fixed
strain-space directions carry the deviatoric response; an elastic bulk term
carries the volumetric response.  State per spring is exactly the paper's
40 bytes: 4 doubles (γ_rev, τ_rev, γ_prev, γ_max) + 2 int32 flags
(loading direction, on-virgin-backbone).  With 150 springs × 4 evaluation
points that is 24 KB/element — the array the heterogeneous memory manager
keeps in host memory and streams (Algorithm 3).

Directions follow Iai's multiple-mechanism form, 3 shear-plane families ×
``nang`` angles: mechanism θ on plane (i,j) senses
γ(θ) = (ε_ii − ε_jj)·cosθ + γ_ij·sinθ.

This module is the *pure-jnp oracle*; kernels/multispring holds the Pallas
TPU kernel validated against it.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# the spring sums are MXU matmuls; on TPU an fp32 dot defaults to one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST

NSPRING_DEFAULT = 150


def spring_directions(nspring: int = NSPRING_DEFAULT) -> tuple[np.ndarray, np.ndarray]:
    """Direction Voigt vectors ``n [S,6]`` and weights ``w [S]``.

    Weights are normalized per plane family so the small-strain response to
    pure shear γ_ij recovers G0 exactly:  Σ_k w_k sin²θ_k = 1.
    Voigt order: xx yy zz xy yz zx (engineering shear).
    """
    assert nspring % 3 == 0, "nspring must be divisible by the 3 shear planes"
    nang = nspring // 3
    theta = (np.arange(nang) + 0.5) * np.pi / nang
    planes = [((0, 1), 3), ((1, 2), 4), ((2, 0), 5)]  # (normal pair, shear slot)
    n = np.zeros((nspring, 6))
    for f, ((i, j), s) in enumerate(planes):
        rows = slice(f * nang, (f + 1) * nang)
        n[rows, i] = np.cos(theta)
        n[rows, j] = -np.cos(theta)
        n[rows, s] = np.sin(theta)
    w = np.full((nspring,), 2.0 / nang)  # Σ w sin² = 1 per family
    return n, w


@dataclasses.dataclass(frozen=True)
class SpringParams:
    """Per-evaluation-point material constants (broadcastable arrays)."""

    G0: Any       # [P] small-strain shear modulus
    gamma_r: Any  # [P] reference strain
    beta: Any     # [P] backbone exponent
    bulk: Any     # [P] elastic bulk modulus
    g_min_frac: float = 1e-3  # tangent floor (fraction of G0), keeps D PSD


jax.tree_util.register_pytree_node(
    SpringParams,
    lambda p: ((p.G0, p.gamma_r, p.beta, p.bulk), p.g_min_frac),
    lambda aux, c: SpringParams(*c, g_min_frac=aux),
)


def init_state(n_points: int, nspring: int = NSPRING_DEFAULT, dtype=jnp.float64):
    """Fresh (virgin) spring state for ``n_points`` evaluation points."""
    z = jnp.zeros((n_points, nspring), dtype)
    return {
        "gamma_rev": z,
        "tau_rev": z,
        "gamma_prev": z,
        "gamma_max": z,
        "direction": jnp.zeros((n_points, nspring), jnp.int32),
        "virgin": jnp.ones((n_points, nspring), jnp.int32),
    }


def state_bytes_per_spring(state) -> int:
    per = 0
    for v in state.values():
        per += np.dtype(v.dtype).itemsize
    return per  # 4*8 + 2*4 = 40 with float64 state


def _backbone(gamma, G0, gamma_r, beta):
    """Modified R-O-type backbone τ(γ) = G0 γ / (1 + |γ/γr|^β).

    β ≤ 1 required: the tangent G0(1+(1−β)x^β)/(1+x^β)² is then strictly
    positive (no softening), so the PSD floor in :func:`update` is a pure
    numerical safeguard and the returned tangent is the exact derivative.
    """
    x = jnp.abs(gamma) / gamma_r
    return G0 * gamma / (1.0 + x**beta)


def _backbone_tangent(gamma, G0, gamma_r, beta):
    """dτ/dγ of the backbone (analytic)."""
    x = jnp.abs(gamma) / gamma_r
    den = 1.0 + x**beta
    return G0 * (1.0 + (1.0 - beta) * x**beta) / (den * den)


def update(
    eps: jnp.ndarray,        # [P,6] total strain (Voigt, engineering shear)
    state: dict[str, jnp.ndarray],
    params: SpringParams,
    n: jnp.ndarray,          # [S,6]
    w: jnp.ndarray,          # [S]
) -> tuple[jnp.ndarray, jnp.ndarray, dict[str, jnp.ndarray]]:
    """One constitutive update: (σ [P,6], D_tan [P,6,6], new state).

    Branch logic (per spring, fully predicated — the Pallas kernel uses the
    same jnp.where structure lane-wise):
      1. detect reversal (direction change) → new Masing branch anchored at
         the previous point,
      2. virgin (|γ| ≥ γ_max) → backbone, else Masing curve
         τ = τ_rev + 2 f((γ−γ_rev)/2),
      3. tangent = branch derivative, floored at g_min_frac·G0.
    """
    G0 = params.G0[:, None]
    gr = params.gamma_r[:, None]
    be = params.beta[:, None]

    gamma = jnp.matmul(eps, n.T, precision=HIGHEST)  # [P,S]
    g_prev = state["gamma_prev"]
    dgam = gamma - g_prev
    moving = jnp.sign(dgam).astype(jnp.int32)
    dir_old = state["direction"]
    # previous branch stress at γ_prev (needed as the new reversal anchor)
    tau_prev_virgin = _backbone(g_prev, G0, gr, be)
    tau_prev_masing = state["tau_rev"] + 2.0 * _backbone(
        0.5 * (g_prev - state["gamma_rev"]), G0, gr, be
    )
    virgin_old = state["virgin"] == 1
    tau_prev = jnp.where(virgin_old, tau_prev_virgin, tau_prev_masing)

    reversal = (moving != 0) & (dir_old != 0) & (moving != dir_old)
    gamma_rev = jnp.where(reversal, g_prev, state["gamma_rev"])
    tau_rev = jnp.where(reversal, tau_prev, state["tau_rev"])
    direction = jnp.where(moving != 0, moving, dir_old)
    virgin = jnp.where(reversal, 0, state["virgin"])

    # rejoin the backbone when exceeding historic maximum strain
    gmax = state["gamma_max"]
    rejoin = jnp.abs(gamma) >= gmax
    virgin = jnp.where(rejoin, 1, virgin)
    gamma_max = jnp.maximum(gmax, jnp.abs(gamma))

    on_bb = virgin == 1
    tau_bb = _backbone(gamma, G0, gr, be)
    tau_ms = tau_rev + 2.0 * _backbone(0.5 * (gamma - gamma_rev), G0, gr, be)
    tau = jnp.where(on_bb, tau_bb, tau_ms)
    gt_bb = _backbone_tangent(gamma, G0, gr, be)
    gt_ms = _backbone_tangent(0.5 * (gamma - gamma_rev), G0, gr, be)
    g_tan = jnp.where(on_bb, gt_bb, gt_ms)
    g_tan = jnp.maximum(g_tan, params.g_min_frac * G0)

    # assemble stress and consistent tangent
    tw = tau * w[None, :]                       # [P,S]
    sigma_dev = jnp.matmul(tw, n, precision=HIGHEST)  # [P,6]
    gw = g_tan * w[None, :]
    # D_dev[p,a,b] = Σ_s gw[p,s] n[s,a] n[s,b]  — an MXU matmul over S
    nn = n[:, :, None] * n[:, None, :]          # [S,6,6]
    D_dev = jnp.einsum("ps,sab->pab", gw, nn, precision=HIGHEST)

    vol_eps = eps[:, :3].sum(axis=1)
    one = jnp.array([1.0, 1.0, 1.0, 0.0, 0.0, 0.0], eps.dtype)
    sigma = sigma_dev + params.bulk[:, None] * vol_eps[:, None] * one[None, :]
    D_vol = params.bulk[:, None, None] * (one[:, None] * one[None, :])[None]
    D = D_dev + D_vol

    new_state = {
        "gamma_rev": gamma_rev,
        "tau_rev": tau_rev,
        "gamma_prev": gamma,
        "gamma_max": gamma_max,
        "direction": direction,
        "virgin": virgin,
    }
    return sigma, D, new_state


def hysteretic_damping(state: dict[str, jnp.ndarray], params: SpringParams) -> jnp.ndarray:
    """Equivalent damping ratio h per evaluation point (drives Rayleigh C^n).

    Hardin–Drnevich style estimate from the secant-modulus degradation at
    the historic max strain: h = h_max·(1 − G_sec/G0); here h_max is folded
    by the caller (material table)."""
    gr = params.gamma_r[:, None]
    be = params.beta[:, None]
    x = (state["gamma_max"] / gr) ** be
    gsec_ratio = 1.0 / (1.0 + x)  # G_sec/G0 on the backbone
    return (1.0 - gsec_ratio).mean(axis=1)  # [P] in [0,1); caller scales by h_max


def material_params_for_mesh(mesh, dtype=jnp.float64) -> SpringParams:
    """Broadcast the per-element material table to evaluation points [E*P]."""
    import numpy as np

    G0 = np.array([m.G0 for m in mesh.materials])[mesh.mat_id]
    gr = np.array([m.gamma_r for m in mesh.materials])[mesh.mat_id]
    be = np.array([m.beta for m in mesh.materials])[mesh.mat_id]
    bk = np.array([m.bulk for m in mesh.materials])[mesh.mat_id]
    P = mesh.wdet.shape[1]
    rep = lambda a: jnp.asarray(np.repeat(a, P), dtype)
    return SpringParams(G0=rep(G0), gamma_r=rep(gr), beta=rep(be), bulk=rep(bk))
