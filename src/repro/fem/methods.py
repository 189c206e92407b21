"""The paper's four solution methods (Algorithms 1–4) as step functions.

  Baseline 1  CRSCPU_MSCPU     stored BCSR + resident spring state
  Baseline 2  CRSGPU_MSCPU     same compute; δu/D round-trip host↔device
                               (multispring "on CPU") — Alg. 2 lines 3/5
  Proposed 1  CRSGPU_MSGPU     spring state host-resident, streamed through
                               the device in npart blocks (Alg. 3)
  Proposed 2  EBEGPU_MSGPU_2SET matrix-free EBE + mixed-precision inner-PCG
                               preconditioner, no CRS update; supports ≥2
                               ensemble sets resident (2SET) via vmap

All four advance the same physics; tests assert trajectory equality.  The
memory placements are real copies on every backend (DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hetmem
from repro.core.stream import StreamEngine, StreamPlan
from repro.fem import assembly, multispring as ms, newmark, quadrature as quad, solver, spmv


@dataclasses.dataclass(frozen=True)
class SeismicConfig:
    dt: float = 0.005
    tol: float = 1e-8
    maxiter: int = 2000
    nspring: int = ms.NSPRING_DEFAULT
    npart: int = 4            # streaming blocks (Alg. 3)
    schedule: str = "serial"  # StreamEngine schedule: serial | prefetch | donate
    prefetch: int = 1         # copy-ahead depth for schedule="prefetch"
    inner_iters: int = 8      # fp32 inner PCG sweeps (EBE-IPCG preconditioner)
    omega0: float = 2.0 * np.pi * 1.0  # Rayleigh target frequency [rad/s]
    dtype: Any = None  # None → fp64 when x64 enabled, else fp32
    # ---- kernel backend dispatch (fem/backend.py) -------------------------
    backend: str = "auto"     # auto | jnp | pallas | pallas_interpret
    ebe_backend: str = ""     # per-kernel override ("" → backend)
    ms_backend: str = ""      # per-kernel override ("" → backend)
    tile_e: int = 512         # Pallas EBE kernel: elements per tile
    tile_p: int = 256         # Pallas multispring kernel: points per tile
    # ---- solver amortization ----------------------------------------------
    warm_start: bool = False  # carry δu as x0 for the next step's CG solve
    precond_every: int = 1    # EBE: refresh the block-Jacobi diag every N steps
    # ---- numerical health (core/health.py) --------------------------------
    health: bool = False      # per-case health word + masked freeze of
    #                           diverged cases (signature-bearing: guarded and
    #                           unguarded campaigns never share checkpoints)

    def __post_init__(self):
        if self.precond_every < 1:
            raise ValueError(f"precond_every must be ≥ 1, got {self.precond_every}")

    @property
    def rdtype(self):
        if self.dtype is not None:
            return self.dtype
        import jax as _jax

        return jnp.float64 if _jax.config.jax_enable_x64 else jnp.float32


class StepAux(NamedTuple):
    iters: jnp.ndarray
    relres: jnp.ndarray
    converged: jnp.ndarray | bool = True
    """CG exit status (:class:`repro.fem.solver.CGResult.converged`):
    False when the solve hit ``maxiter`` above tolerance or went
    non-finite — the signal the health layer folds into its per-case word."""


def _material_tables(mesh, cfg):
    params = ms.material_params_for_mesh(mesh, cfg.rdtype)
    h_max = jnp.asarray(
        np.array([m.h_max for m in mesh.materials])[mesh.mat_id], cfg.rdtype
    )  # [E]
    return params, h_max


def _spring_dirs(cfg):
    n, w = ms.spring_directions(cfg.nspring)
    return n, w


class FemOperators:
    """Mesh-bound jnp closures shared by all four methods."""

    def __init__(self, mesh, cfg: SeismicConfig, element_kernel=None, multispring_fn=None):
        self.mesh = mesh
        self.cfg = cfg
        dt = cfg.rdtype
        self.mass = jnp.asarray(mesh.mass, dt)
        self.dash = jnp.asarray(mesh.dashpot, dt)
        self.force_map = jnp.asarray(mesh.force_map, dt)
        self.Jinv = jnp.asarray(mesh.Jinv, dt)
        self.wdet = jnp.asarray(mesh.wdet, dt)
        n, w = _spring_dirs(cfg)
        self.n_dirs = jnp.asarray(n, dt)
        self.w_dirs = jnp.asarray(w, dt)
        self.params, self.h_max = _material_tables(mesh, cfg)
        self.nnzb = mesh.col_idx.shape[0]
        self.points = spmv.point_tables(mesh, dt)
        self.element_kernel = element_kernel
        self.multispring_fn = multispring_fn or ms.update
        # set by fem.backend.make_operators — None means "constructed bare"
        # (kernel args passed explicitly, or the legacy jnp-oracle default)
        self.kernel_backend = None

    # ---- constitutive -----------------------------------------------------
    @jax.named_scope("repro.multispring")
    def multispring_all(self, eps_pts, spring_state):
        return self.multispring_fn(eps_pts, spring_state, self.params, self.n_dirs, self.w_dirs)

    @jax.named_scope("repro.multispring")
    def multispring_block(self, blk, eps_blk, params_blk):
        """Per-streamed-block wrapper: blk is the spring-state leaf list.

        Everything the rest of the step needs (σ, D, damping fraction) is
        computed *on device before* θ_j returns to host — Algorithm 3 keeps
        only θ round-tripping."""
        state = dict(zip(self._state_keys, blk))
        sigma, D, new_state = self.multispring_fn(
            eps_blk, state, params_blk, self.n_dirs, self.w_dirs
        )
        frac = ms.hysteretic_damping(new_state, params_blk)
        return [new_state[k] for k in self._state_keys], (sigma, D, frac)

    _state_keys = ("gamma_rev", "tau_rev", "gamma_prev", "gamma_max", "direction", "virgin")

    def init_springs(self, n_points):
        return ms.init_state(n_points, self.cfg.nspring, self.cfg.rdtype)

    def block_params(self, npart):
        """SpringParams sliced per streamed block (static).

        ``npart`` must divide the quadrature-point count — the same contract
        as :func:`hetmem.partition_arrays`, enforced here too so a bad
        ``npart`` fails loudly instead of silently dropping trailing points.
        """
        P = self.params
        E, Q = self.mesh.n_elem, quad.NPOINT
        npts = E * Q
        chunk = hetmem.check_divisible(npts, npart, "quadrature point count")
        out = []
        for j in range(npart):
            s = slice(j * chunk, (j + 1) * chunk)
            out.append(ms.SpringParams(P.G0[s], P.gamma_r[s], P.beta[s], P.bulk[s], P.g_min_frac))
        return out

    # ---- damping ----------------------------------------------------------
    @jax.named_scope("repro.damping")
    def damping_from_frac(self, frac):
        """(α, β_e): Rayleigh from per-point damping fractions [E*P]."""
        h_pt = frac.reshape(self.mesh.n_elem, quad.NPOINT).mean(axis=1) * self.h_max
        beta_e = 2.0 * h_pt / self.cfg.omega0
        alpha = 2.0 * jnp.mean(h_pt) * self.cfg.omega0
        return alpha, beta_e

    @jax.named_scope("repro.damping")
    def damping_coeffs(self, spring_state):
        """(α, β_e) from a resident spring state."""
        return self.damping_from_frac(ms.hysteretic_damping(spring_state, self.params))

    # ---- operators ---------------------------------------------------------
    @jax.named_scope("repro.crs_update")
    def crs_update(self, D, beta_e, alpha):
        """UpdateCRS: assemble A's BCSR values + block-Jacobi inverse."""
        Kb = assembly.element_blocks(D, self.Jinv, self.wdet)    # 9 × [10,10,E]
        coef = 1.0 + (2.0 / self.cfg.dt) * beta_e
        valA = assembly.assemble_bcsr([K * coef for K in Kb], self.mesh.entry_map, self.nnzb)
        diag_add = (
            (4.0 / self.cfg.dt**2 + 2.0 * alpha / self.cfg.dt) * self.mass[:, None]
            + (2.0 / self.cfg.dt) * self.dash
        )
        valA = assembly.add_diag(valA, self.mesh.diag_slots, diag_add)
        # separate K values for C·v in the RHS (β-weighted) — the damping matvec
        valCk = assembly.assemble_bcsr([K * beta_e for K in Kb], self.mesh.entry_map, self.nnzb)
        Minv = assembly.block_jacobi_inverse(valA, self.mesh.diag_slots)
        return valA, valCk, Minv

    def crs_matvec(self, valA):
        def mv(xflat):
            x = solver.unflat(xflat)
            return solver.flat(spmv.bcsr_matvec(valA, self.mesh.rowids, self.mesh.col_idx, x))
        return mv

    def cv_matvec_crs(self, valCk, alpha):
        def mv(v):
            kv = spmv.bcsr_matvec(valCk, self.mesh.rowids, self.mesh.col_idx, v)
            return alpha * self.mass[:, None] * v + kv + self.dash * v
        return mv

    def ebe_matvec_A(self, D, beta_e, alpha):
        coef = 1.0 + (2.0 / self.cfg.dt) * beta_e
        diag = (
            (4.0 / self.cfg.dt**2 + 2.0 * alpha / self.cfg.dt) * self.mass[:, None]
            + (2.0 / self.cfg.dt) * self.dash
        )

        def mv(xflat):
            # dtype-follows-input: the same closure serves the fp64 outer CG
            # and the fp32 inner preconditioner (mixed precision, paper [9])
            x = solver.unflat(xflat)
            y = spmv.ebe_matvec(
                x, D.astype(x.dtype), self.mesh, coef.astype(x.dtype),
                element_kernel=self.element_kernel,
            )
            return solver.flat(y + diag.astype(x.dtype) * x)

        return mv

    def cv_matvec_ebe(self, D, beta_e, alpha):
        def mv(v):
            kv = spmv.ebe_matvec(v, D, self.mesh, beta_e, element_kernel=self.element_kernel)
            return alpha * self.mass[:, None] * v + kv + self.dash * v
        return mv

    @jax.named_scope("repro.ebe_diag")
    def ebe_diag_inverse(self, D, beta_e, alpha):
        """Block-Jacobi of A without assembling K (nodal diag blocks only),
        lane-major ``[9, N]`` like :func:`assembly.block_jacobi_inverse`.

        The diagonal blocks (n = m) of :func:`assembly.element_blocks`, with
        the element index minor, so no ``[E, small, small]`` product is
        formed (see assembly.py)."""
        dt = D.dtype
        coef = 1.0 + (2.0 / self.cfg.dt) * beta_e
        K = assembly.element_blocks(D, self.Jinv, self.wdet * coef[:, None],
                                    diagonal=True)            # 9 × [10,E]
        N = self.mesh.n_nodes
        idx = jnp.asarray(self.mesh.conn.T.reshape(-1))       # (node slot, elem)
        diag_add = (
            (4.0 / self.cfg.dt**2 + 2.0 * alpha / self.cfg.dt) * self.mass[:, None]
            + (2.0 / self.cfg.dt) * self.dash
        ).astype(dt)                                          # [N,3]
        nodal = [jnp.zeros(N, dt).at[idx].add(K[3 * a + b].reshape(-1))
                 + (diag_add[:, a] if a == b else 0.0)
                 for a in range(3) for b in range(3)]
        return assembly.inv3(nodal)


# ---------------------------------------------------------------------------
# step factories — each returns step(carry, f_ext) -> (carry, aux)
# ---------------------------------------------------------------------------


def _strain_pts(ops, u):
    return spmv.strain_at_points(u, ops.points)


def _resident_multispring(ops, eps_pts, springs):
    sigma, D, springs = ops.multispring_all(eps_pts, springs)
    return sigma, D.reshape(ops.mesh.n_elem, quad.NPOINT, 6, 6), springs


def _streamed_multispring(ops, eps_pts, springs_ps, block_params, offload=True):
    """Algorithm 3 via the StreamEngine: θ blocks host↔device, σ/D on device."""
    cfg = ops.cfg
    npart = len(springs_ps.blocks)
    npts = eps_pts.shape[0]
    chunk = hetmem.check_divisible(npts, npart, "quadrature point count")
    eps_blocks = [eps_pts[j * chunk : (j + 1) * chunk] for j in range(npart)]
    plan = StreamPlan(
        npart=npart,
        schedule=cfg.schedule,
        prefetch=cfg.prefetch,
        offload=offload,
        collect=True,
    )
    res = StreamEngine(plan).run(
        ops.multispring_block,
        springs_ps,
        per_block=(eps_blocks, block_params),
    )
    new_ps, extras = res.state, res.extras
    sigma = jnp.concatenate([e[0] for e in extras], axis=0)
    D = jnp.concatenate([e[1] for e in extras], axis=0)
    frac = jnp.concatenate([e[2] for e in extras], axis=0)
    return sigma, D.reshape(ops.mesh.n_elem, quad.NPOINT, 6, 6), frac, new_ps


def partition_springs(ops, springs, npart):
    """Element-point-contiguous partition of spring state (hetmem blocks)."""
    parts = hetmem.partition_arrays(springs, npart)
    blocks = [[p[k] for k in FemOperators._state_keys] for p in parts]
    from repro.utils.tree import BlockSpec

    # one leaf per (block, key): treedef of the dict restored on unpartition
    spec = BlockSpec(treedef=None, block_of=(), npart=npart)
    return hetmem.PartitionedState(blocks=blocks, spec=spec)


def springs_to_host(ps: hetmem.PartitionedState) -> hetmem.PartitionedState:
    return hetmem.PartitionedState(
        blocks=[hetmem.put_host(b) for b in ps.blocks], spec=ps.spec
    )


def make_step_crs(ops: FemOperators, *, transfer_boundaries: bool = False,
                  streamed: bool = False, offload: bool = True):
    """Baseline 1 (plain), Baseline 2 (transfer_boundaries), Proposed 1 (streamed).

    With ``cfg.warm_start`` the carry grows a trailing ``du_prev`` leaf and
    each step's PCG starts from the previous step's solution (the Newmark
    predictor: δu changes slowly relative to the CG tolerance, so the warm
    start removes the iterations spent rediscovering it from zero).
    """
    cfg = ops.cfg
    block_params = ops.block_params(cfg.npart) if streamed else None

    def step(carry, f_t):
        nm, springs, D, alpha, beta_e, *extra = carry
        x0 = extra[0] if cfg.warm_start else None
        valA, valCk, Minv = ops.crs_update(D, beta_e, alpha)          # UpdateCRS
        f_ext = ops.force_map * f_t[None, :]
        b = newmark.rhs(nm, f_ext, ops.mass, cfg.dt, ops.cv_matvec_crs(valCk, alpha))
        res = solver.pcg(
            ops.crs_matvec(valA),
            solver.flat(b),
            solver.block_jacobi_apply(Minv),
            tol=cfg.tol,
            maxiter=cfg.maxiter,
            x0=x0,
        )
        du = solver.unflat(res.x)
        u_new = nm.u + du
        eps_pts = _strain_pts(ops, u_new)
        if streamed:
            sigma, D_new, frac, springs = _streamed_multispring(
                ops, eps_pts, springs, block_params, offload=offload
            )
        elif transfer_boundaries:
            # Alg. 2: strain → host, Multispring *computed on the host CPU*,
            # tangent D → device.  compute_on stages the host computation and
            # XLA inserts the boundary transfers (δu down, D up).
            from jax.experimental.compute_on import compute_on

            with compute_on("device_host"):
                sigma, D_new, springs = _resident_multispring(ops, eps_pts, springs)
            sigma, D_new = hetmem.to_device((sigma, D_new))
        else:
            sigma, D_new, springs = _resident_multispring(ops, eps_pts, springs)
        q_new = spmv.internal_force(sigma, ops.points)
        nm = newmark.advance(nm, du, q_new, cfg.dt)
        if streamed:
            alpha, beta_e = ops.damping_from_frac(frac)
        else:
            alpha, beta_e = ops.damping_coeffs(springs)
        tail = (res.x,) if cfg.warm_start else ()
        return (nm, springs, D_new, alpha, beta_e, *tail), StepAux(res.iters, res.relres, res.converged)

    return step


def make_step_ebe(ops: FemOperators, *, streamed: bool = True, offload: bool = True):
    """Proposed 2: EBE matrix-free solver + streamed multispring, no CRS.

    Solver amortization (both off by default, both signature-bearing):

    * ``cfg.warm_start`` — the carry grows a ``du_prev`` leaf used as the
      flexible-CG ``x0`` (Newmark predictor start);
    * ``cfg.precond_every = N > 1`` — the carry grows ``(Minv, step)``
      leaves and :meth:`FemOperators.ebe_diag_inverse` (the full
      ``[E,P,6,30]`` B-matrix einsum + segment-sum + batched 3×3 inverse)
      is recomputed only on steps where ``step % N == 0``; in between the
      *lagged* diagonal from the carry preconditions the solve.  The lag is
      admissible because flexible CG tolerates an inexact preconditioner —
      the trajectory stays within solver tolerance while the per-step
      setup cost drops N-fold.  (Under ``vmap`` — the campaign's k-set
      batching — ``lax.cond`` lowers to ``select``, so the rebuild is
      traded for trajectory-identical semantics rather than time there;
      the per-case scan path gets the full saving.)
    """
    cfg = ops.cfg
    block_params = ops.block_params(cfg.npart) if streamed else None
    lag = cfg.precond_every > 1

    def step(carry, f_t):
        nm, springs, D, alpha, beta_e, *extra = carry
        x0 = extra[0] if cfg.warm_start else None
        mvA = ops.ebe_matvec_A(D, beta_e, alpha)
        if lag:
            Minv_prev, tstep = extra[-2], extra[-1]
            Minv = jax.lax.cond(
                tstep % cfg.precond_every == 0,
                lambda: ops.ebe_diag_inverse(D, beta_e, alpha),
                lambda: Minv_prev,
            )
        else:
            Minv = ops.ebe_diag_inverse(D, beta_e, alpha)
        inner = solver.make_inner_pcg_preconditioner(
            mvA,  # dtype-follows-input → fp32 inside the inner solve
            solver.block_jacobi_apply(Minv.astype(jnp.float32)),
            inner_iters=cfg.inner_iters,
        )
        f_ext = ops.force_map * f_t[None, :]
        b = newmark.rhs(nm, f_ext, ops.mass, cfg.dt, ops.cv_matvec_ebe(D, beta_e, alpha))
        res = solver.fcg(mvA, solver.flat(b), inner, tol=cfg.tol, maxiter=cfg.maxiter, x0=x0)
        du = solver.unflat(res.x)
        u_new = nm.u + du
        eps_pts = _strain_pts(ops, u_new)
        if streamed:
            sigma, D_new, frac, springs = _streamed_multispring(
                ops, eps_pts, springs, block_params, offload=offload
            )
            alpha, beta_e = ops.damping_from_frac(frac)
        else:
            sigma, D_new, springs = _resident_multispring(ops, eps_pts, springs)
            alpha, beta_e = ops.damping_coeffs(springs)
        q_new = spmv.internal_force(sigma, ops.points)
        nm = newmark.advance(nm, du, q_new, cfg.dt)
        tail = (res.x,) if cfg.warm_start else ()
        if lag:
            tail += (Minv, tstep + 1)
        return (nm, springs, D_new, alpha, beta_e, *tail), StepAux(res.iters, res.relres, res.converged)

    return step


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def initial_carry(ops: FemOperators, *, streamed: bool = False, host: bool = True,
                  ebe: bool = False):
    """Elastic initial tangent + virgin springs (+ host placement if streamed).

    The carry layout follows the config: ``cfg.warm_start`` appends a zero
    ``du_prev`` leaf, and ``ebe=True`` with ``cfg.precond_every > 1``
    appends the lagged ``(Minv, step)`` pair.  The seed ``Minv`` is zeros —
    it only fixes the pytree structure: step 0's ``tstep % N == 0`` branch
    always recomputes the real diagonal before anything reads it."""
    cfg = ops.cfg
    npts = ops.mesh.n_elem * quad.NPOINT
    springs = ops.init_springs(npts)
    eps0 = jnp.zeros((npts, 6), cfg.rdtype)
    _, D0, _ = ops.multispring_all(eps0, springs)
    D0 = D0.reshape(ops.mesh.n_elem, quad.NPOINT, 6, 6)
    alpha, beta_e = ops.damping_coeffs(springs)
    nm = newmark.init_state(ops.mesh.n_nodes, cfg.rdtype)
    tail = ()
    if cfg.warm_start:
        tail += (jnp.zeros(3 * ops.mesh.n_nodes, cfg.rdtype),)
    if ebe and cfg.precond_every > 1:
        tail += (jnp.zeros((9, ops.mesh.n_nodes), cfg.rdtype),
                 jnp.zeros((), jnp.int32))
    if streamed:
        ps = partition_springs(ops, springs, cfg.npart)
        if host and hetmem.host_memory_available():
            ps = springs_to_host(ps)
        springs = ps
    return (nm, springs, D0, alpha, beta_e, *tail)


METHODS = ("baseline1", "baseline2", "proposed1", "proposed2")


def make_step(name: str, ops: FemOperators, offload: bool = True):
    if name == "baseline1":
        return make_step_crs(ops), False
    if name == "baseline2":
        return make_step_crs(ops, transfer_boundaries=True), False
    if name == "proposed1":
        return make_step_crs(ops, streamed=True, offload=offload), True
    if name == "proposed2":
        return make_step_ebe(ops, streamed=True, offload=offload), True
    raise KeyError(name)


def run(
    mesh,
    cfg: SeismicConfig,
    wave: jnp.ndarray,  # [nt,3] bedrock input velocity
    method: str = "proposed2",
    observe: np.ndarray | None = None,  # node ids to record
    offload: bool = True,
    element_kernel=None,
    multispring_fn=None,
) -> dict[str, Any]:
    """Run a full nonlinear time-history analysis with the chosen method.

    Kernels resolve through the dispatch layer (:mod:`repro.fem.backend`,
    ``cfg.backend``); explicit ``element_kernel``/``multispring_fn`` still
    override it.
    """
    from repro.fem import backend as _backend

    ops = _backend.make_operators(
        mesh, cfg, element_kernel=element_kernel, multispring_fn=multispring_fn
    )
    step, streamed = make_step(method, ops, offload=offload)
    carry = initial_carry(
        ops, streamed=streamed, host=offload, ebe=method == "proposed2"
    )
    obs_idx = jnp.asarray(observe if observe is not None else mesh.surface[:1])

    @jax.jit
    def step_obs(carry, f_t):
        carry, aux = step(carry, f_t)
        nm = carry[0]
        return carry, (aux, nm.v[obs_idx])

    wave = jnp.asarray(wave, cfg.rdtype)
    carry, (auxes, vel) = jax.lax.scan(step_obs, carry, wave)
    nm = carry[0]
    return {
        "u": nm.u,
        "v": nm.v,
        "velocity_history": vel,  # [nt, n_obs, 3]
        "iters": auxes.iters,
        "relres": auxes.relres,
        "springs": carry[1],  # final θ: host blocks when streamed + offload
    }


def make_ensemble_step(ops: FemOperators, method: str, *, offload: bool = False):
    """(step, carry0) for one ensemble member — carry always matches the step.

    ``proposed2`` takes its device-resident 2SET limit (Alg. 4): resident
    springs, no streaming — the regime the k-set residency batches.  Every
    other name keeps its :func:`make_step` form (``proposed1`` streams a
    :class:`~repro.core.hetmem.PartitionedState`, so it gets the matching
    ``streamed`` carry, not a resident spring dict).  Raises ``KeyError`` for
    names outside :data:`METHODS`.
    """
    if method == "proposed2":
        step, streamed = make_step_ebe(ops, streamed=False), False
    else:
        step, streamed = make_step(method, ops, offload=offload)
    carry0 = initial_carry(
        ops, streamed=streamed, host=False, ebe=method == "proposed2"
    )
    return step, carry0


def run_ensemble(
    mesh,
    cfg: SeismicConfig,
    waves,  # [M, nt, 3] — M independent earthquake cases
    observe: np.ndarray | None = None,
    method: str = "proposed2",
):
    """2SET (Alg. 4): batch M ensemble cases through one device residency.

    The paper loads two problem sets at once into the GPU memory freed by
    EBE; the TPU-native form is a k-set axis over the case dimension — every
    solver iterate and constitutive update runs batched, doubling (M-fold)
    arithmetic intensity at the memory cost of M state sets.  The ensemble
    axis is the StreamEngine's ``kset``: here in its device-resident limit
    (``npart=1``, no transfers, :meth:`StreamEngine.kmap`); the streamed
    k-set regime (members' θ blocks stacked and streamed together) is what
    surrogate/dataset.py batches through when M sets don't fit.  For
    sharded multi-round campaigns with checkpoint/resume, see
    :mod:`repro.campaign`.
    """
    from repro.fem import backend as _backend

    ops = _backend.make_operators(mesh, cfg)
    step, carry0 = make_ensemble_step(ops, method)
    obs_idx = jnp.asarray(observe if observe is not None else mesh.surface[:1])

    def one_case(wave):
        def body(c, f_t):
            c, aux = step(c, f_t)
            return c, (aux, c[0].v[obs_idx])

        carry, (auxes, vel) = jax.lax.scan(body, carry0, wave)
        return vel, auxes.iters

    waves = jnp.asarray(waves, cfg.rdtype)
    M = waves.shape[0]
    engine = StreamEngine(StreamPlan(npart=1, offload=False, kset=M))
    vel, iters = jax.jit(lambda w: engine.kmap(one_case, w))(waves)
    return {"velocity_history": vel, "iters": iters}  # [M, nt, n_obs, 3]
