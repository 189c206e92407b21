"""Per-case numerical health: detect, freeze, and quarantine diverged cases.

A campaign advances many independent cases batched through one ``vmap``
(the k-set axis).  When one case's constitutive update or CG solve goes
non-finite, nothing in plain arithmetic stops the NaN from marching
forward in *time* — every subsequent step of that case computes on
garbage, the garbage lands in the committed dataset shards, and the
surrogate trains on it.  (Siblings in the vmap are arithmetically
independent — batching itself does not mix lanes — but an unflagged
diverged lane is indistinguishable from a healthy one downstream.)

This module is the detection + containment layer:

* a per-case **health word** — an int32 bitmask of everything that has
  gone wrong for that case so far (sticky: bits set, never cleared);
* :func:`guard_step` — wraps a per-case FEM step so that after each step
  the word updates from (carry finiteness, spring-state finiteness, CG
  convergence) and, once a *fatal* bit trips, the case's carry is
  **frozen** via masked arithmetic (``jnp.where`` per leaf): the step
  keeps executing under vmap — unavoidable — but its output is discarded
  and the last healthy state is carried forward, so non-finite values
  never enter the carry and the case's observables stay finite;
* helpers the campaign/planner layers use to report and exclude
  (:func:`diverged`, :func:`describe`).

Everything is scan/vmap-safe; the word and the non-converged-step counter
ride the scan carry, so checkpoints capture them and kill-and-resume
stays bit-identical with guards enabled.
"""
from __future__ import annotations

from functools import reduce

import jax
import jax.numpy as jnp

# -- health word bits --------------------------------------------------------
BIT_CARRY_NONFINITE = 1    # non-finite value somewhere in the step carry
BIT_SPRINGS_NONFINITE = 2  # non-finite constitutive (multispring) state
BIT_SOLVER_NONFINITE = 4   # CG produced a non-finite residual/solution
BIT_NONCONVERGED = 8       # CG hit maxiter with relres > tol (informational)

#: bits that freeze a case and exclude it from shard output
FATAL = BIT_CARRY_NONFINITE | BIT_SPRINGS_NONFINITE | BIT_SOLVER_NONFINITE

_BIT_NAMES = {
    BIT_CARRY_NONFINITE: "carry_nonfinite",
    BIT_SPRINGS_NONFINITE: "springs_nonfinite",
    BIT_SOLVER_NONFINITE: "solver_nonfinite",
    BIT_NONCONVERGED: "nonconverged",
}


def init_word():
    """A healthy (all-clear) health word."""
    return jnp.zeros((), jnp.int32)


def is_live(word):
    """True while no fatal bit has tripped (the case still advances)."""
    return (word & FATAL) == 0


def diverged(word) -> jnp.ndarray:
    """Elementwise: has this case tripped a fatal bit?"""
    return (jnp.asarray(word) & FATAL) != 0


def describe(word: int) -> str:
    """Human-readable bit list for manifests/logs (``"healthy"`` if 0)."""
    bits = [name for bit, name in _BIT_NAMES.items() if int(word) & bit]
    return "+".join(bits) if bits else "healthy"


def finite_all(tree) -> jnp.ndarray:
    """Scalar bool: every inexact leaf of ``tree`` is finite.

    Integer/bool leaves (spring direction flags, lagged step counters) are
    finite by construction and skipped.
    """
    checks = [
        jnp.all(jnp.isfinite(leaf))
        for leaf in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)
    ]
    if not checks:
        return jnp.asarray(True)
    return reduce(jnp.logical_and, checks)


def freeze(live, new_tree, old_tree):
    """``new_tree`` where ``live`` else ``old_tree``, leafwise.

    ``live`` is a scalar bool per case (inside vmap) — ``jnp.where``
    broadcasts it against every leaf shape and dtype, so a tripped case's
    entire carry (Newmark state, springs, tangent, warm-start/lag tails)
    reverts to its last healthy value in one masked select.
    """
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(live, n, o), new_tree, old_tree
    )


def update_word(word, new_carry, springs, aux):
    """Fold one step's outcome into the health word (sticky bits)."""
    trip = jnp.where(finite_all(new_carry), 0, BIT_CARRY_NONFINITE)
    trip = trip | jnp.where(finite_all(springs), 0, BIT_SPRINGS_NONFINITE)
    solver_bad = ~jnp.isfinite(aux.relres)
    trip = trip | jnp.where(solver_bad, BIT_SOLVER_NONFINITE, 0)
    trip = trip | jnp.where(aux.converged, 0, BIT_NONCONVERGED)
    return word | trip.astype(jnp.int32)


def initial_guard_carry(carry):
    """Wrap a bare step carry for :func:`guard_step`:
    ``(carry, word, nonconverged_steps)``."""
    return (carry, init_word(), jnp.zeros((), jnp.int32))


def guard_step(step, *, springs_index: int = 1):
    """Wrap ``step(carry, f_t) -> (carry', aux)`` with health tracking.

    The wrapped step operates on ``(carry, word, ncg)`` — see
    :func:`initial_guard_carry`.  ``springs_index`` locates the
    constitutive-state element inside the carry tuple (the FEM step
    factories keep springs at position 1).  ``aux`` must expose ``relres``
    and ``converged`` (:class:`repro.fem.methods.StepAux`).
    """

    def wrapped(hcarry, f_t):
        inner, word, ncg = hcarry
        new_inner, aux = step(inner, f_t)
        with jax.named_scope("repro.health"):
            live_before = is_live(word)
            word_new = jnp.where(
                live_before,
                update_word(word, new_inner, new_inner[springs_index], aux),
                word,
            )
            frozen = freeze(is_live(word_new), new_inner, inner)
            # count genuine maxiter exhaustion only while the case is live
            # (a non-finite residual trips BIT_SOLVER_NONFINITE instead)
            ncg_new = ncg + jnp.where(
                live_before & ~aux.converged & jnp.isfinite(aux.relres), 1, 0
            ).astype(ncg.dtype)
        return (frozen, word_new, ncg_new), aux

    return wrapped
