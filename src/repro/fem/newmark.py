"""Newmark-β (β=1/4) recurrences of the paper's Eq. (1).

    A δu = f^n − q^{n−1} + C v^{n−1} + M (a^{n−1} + 4/dt v^{n−1})
    A    = 4/dt² M + 2/dt C + K
    u^n  = u^{n−1} + δu
    v^n  = −v^{n−1} + 2/dt δu
    a^n  = −a^{n−1} − 4/dt v^{n−1} + 4/dt² δu

C = α M + Σ_e β_e K_e + diag(dashpot): Rayleigh damping from the current
hysteretic damping levels (α global, β_e element-wise) plus the Lysmer
absorbing dashpots.  q is the assembled internal force from the multi-spring
stresses (the consistent nonlinear form of the paper's q recurrence).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class NewmarkState(NamedTuple):
    u: jnp.ndarray  # [N,3]
    v: jnp.ndarray
    a: jnp.ndarray
    q: jnp.ndarray  # internal force [N,3]


def init_state(n_nodes: int, dtype=jnp.float64) -> NewmarkState:
    z = jnp.zeros((n_nodes, 3), dtype)
    return NewmarkState(u=z, v=z, a=z, q=z)


@jax.named_scope("repro.newmark")
def rhs(
    state: NewmarkState,
    f_ext: jnp.ndarray,
    mass: jnp.ndarray,      # [N]
    dt: float,
    cv_matvec: Callable[[jnp.ndarray], jnp.ndarray],  # x ↦ C x
) -> jnp.ndarray:
    m = mass[:, None]
    return (
        f_ext
        - state.q
        + cv_matvec(state.v)
        + m * (state.a + (4.0 / dt) * state.v)
    )


@jax.named_scope("repro.newmark")
def advance(state: NewmarkState, du: jnp.ndarray, q_new: jnp.ndarray, dt: float) -> NewmarkState:
    v_new = -state.v + (2.0 / dt) * du
    a_new = -state.a - (4.0 / dt) * state.v + (4.0 / dt**2) * du
    return NewmarkState(u=state.u + du, v=v_new, a=a_new, q=q_new)


def a_coefficients(dt: float, alpha: float) -> tuple[float, float]:
    """(c_m, c_d): A = c_m·diag(m) + c_d·diag(dash) + Σ_e (1+2β_e/dt) K_e.

    c_m folds the mass term and the α-Rayleigh part of C;
    c_d is the dashpot's 2/dt factor.
    """
    return 4.0 / dt**2 + 2.0 * alpha / dt, 2.0 / dt
