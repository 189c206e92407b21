"""Plain float64 numpy reference of one implicit step of the nonlinear FEM.

It imports nothing of the program.  From the configuration (box, layer
materials, time step, springs, Rayleigh target) and the mesh's raw data
(node coordinates, TET10 connectivity, element material ids: the problem's
input, as rows are a database's) it builds every table itself: quadrature,
shape-function gradients, HRZ lumped mass, Lysmer dashpots, bedrock force
map, spring directions and per-point material constants.

The semantics it follows (the paper's Eq. (1) with Newmark β = 1/4):

    A δu = f − q + C v + M (a + 4/dt v)
    A    = (4/dt² + 2α/dt) M + 2/dt Dash + Σ_e (1 + 2β_e/dt) K_e(D)
    C    = α M + Σ_e β_e K_e(D) + Dash
    u' = u + δu,  v' = −v + 2/dt δu,  a' = −a − 4/dt v + 4/dt² δu

with the Iai multi-spring law (modified Ramberg–Osgood backbone, Masing
branches) giving σ, the tangent D and the spring history θ at every Gauss
point, and Rayleigh damping from the hysteretic damping level.

``check_step`` takes the state a program had before a step and after it,
and measures how far the program's step is from these equations.
``control_step`` computes the step itself, with every stored value rounded
to a lower precision: the control that a sound check must refuse.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses

import numpy as np

# 4-point degree-2 Gauss rule on the reference tetrahedron (volume 1/6)
_GA = (5.0 + 3.0 * np.sqrt(5.0)) / 20.0
_GB = (5.0 - np.sqrt(5.0)) / 20.0
BARY = np.array([[_GA, _GB, _GB, _GB], [_GB, _GA, _GB, _GB],
                 [_GB, _GB, _GA, _GB], [_GB, _GB, _GB, _GA]])
NPOINT = 4
# TET10 node order: 4 corners, then mid-edges of these corner pairs
EDGES = ((0, 1), (1, 2), (0, 2), (0, 3), (1, 3), (2, 3))
# Voigt index of the symmetric tensor entry (i, j), engineering shear
VOIGT = np.array([[0, 3, 5], [3, 1, 4], [5, 4, 2]])
LEAVES = ("gamma_rev", "tau_rev", "gamma_prev", "gamma_max")
FLAGS = ("direction", "virgin")


def _shape(bary):
    L = bary
    corner = L * (2.0 * L - 1.0)
    edge = np.stack([4.0 * L[:, a] * L[:, b] for a, b in EDGES], axis=1)
    return np.concatenate([corner, edge], axis=1)                 # [P,10]


def _dshape_dxi(bary):
    """∂N/∂ξ with ξ = (L2, L3, L4), L1 = 1 − Σξ → [P,10,3]."""
    P = bary.shape[0]
    dL = np.zeros((P, 10, 4))
    for i in range(4):
        dL[:, i, i] = 4.0 * bary[:, i] - 1.0
    for k, (a, b) in enumerate(EDGES):
        dL[:, 4 + k, a] = 4.0 * bary[:, b]
        dL[:, 4 + k, b] = 4.0 * bary[:, a]
    return dL[:, :, 1:] - dL[:, :, :1]


@dataclasses.dataclass
class Tables:
    conn: np.ndarray      # [E,10]
    g: np.ndarray         # [E,P,10,3] ∂N_n/∂x_j
    wdet: np.ndarray      # [E,P]
    mass: np.ndarray      # [N]
    dash: np.ndarray      # [N,3]
    force: np.ndarray     # [N,3]
    n: np.ndarray         # [S,6] spring directions
    w: np.ndarray         # [S]
    G0: np.ndarray        # [E*P] per point
    gr: np.ndarray
    be: np.ndarray
    bulk: np.ndarray
    h_max: np.ndarray     # [E]
    dt: float
    omega0: float
    g_min: float
    n_nodes: int


def build_tables(cfg: dict, coords, conn, mat_id) -> Tables:
    coords = np.asarray(coords, np.float64)
    conn = np.asarray(conn, np.int64)
    mats = cfg["materials"]
    mat_id = np.asarray(mat_id)
    x = coords[conn[:, :4]]
    J = np.stack([x[:, 1] - x[:, 0], x[:, 2] - x[:, 0], x[:, 3] - x[:, 0]], axis=1)
    detJ = np.linalg.det(J)
    if not (detJ > 0).all():
        raise ValueError("reference: an element has non-positive volume")
    Jinv = np.linalg.inv(J)
    g = np.einsum("pnk,ekj->epnj", _dshape_dxi(BARY), Jinv)
    wdet = np.outer(detJ / 6.0, np.full(NPOINT, 0.25))
    N = coords.shape[0]
    # HRZ lumping: the consistent diagonal scaled to the element's mass
    Ns = _shape(BARY)
    rho_e = np.array([m["rho"] for m in mats])[mat_id]
    diag = np.einsum("ep,pn->en", wdet, Ns * Ns)
    m_e = diag * (rho_e * wdet.sum(1) / diag.sum(1))[:, None]
    mass = np.bincount(conn.ravel(), m_e.ravel(), minlength=N)
    # Lysmer dashpots on the bottom and the sides, lumped per node; the
    # bedrock input enters as 2·ρV·A times the input velocity
    lx, ly, lz = (cfg["mesh"][k] for k in ("lx", "ly", "lz"))
    eps = 1e-9
    z, xx, yy = coords[:, 2], coords[:, 0], coords[:, 1]
    bottom = z < -lz + eps
    side = (xx < eps) | (xx > lx - eps) | (yy < eps) | (yy > ly - eps)
    rock = mats[-1]
    a_bot = lx * ly / max(1, bottom.sum())
    a_side = 2.0 * (lx + ly) * lz / max(1, side.sum())
    bot_c = rock["rho"] * np.array([rock["vs"], rock["vs"], rock["vp"]])
    dash = np.zeros((N, 3))
    dash[bottom] += a_bot * bot_c
    dash[side] += a_side * rock["rho"] * rock["vs"]
    force = np.zeros((N, 3))
    force[bottom] = 2.0 * a_bot * bot_c
    # Iai multiple-mechanism directions: 3 shear planes × nang angles
    S = int(cfg["nspring"])
    nang = S // 3
    th = (np.arange(nang) + 0.5) * np.pi / nang
    nd = np.zeros((S, 6))
    for f, ((i, j), s) in enumerate((((0, 1), 3), ((1, 2), 4), ((2, 0), 5))):
        r = slice(f * nang, (f + 1) * nang)
        nd[r, i], nd[r, j], nd[r, s] = np.cos(th), -np.cos(th), np.sin(th)
    w = np.full(S, 2.0 / nang)
    per = lambda key: np.repeat(np.array([key(m) for m in mats])[mat_id], NPOINT)
    return Tables(
        conn=conn, g=g, wdet=wdet, mass=mass, dash=dash, force=force, n=nd, w=w,
        G0=per(lambda m: m["rho"] * m["vs"] ** 2),
        gr=per(lambda m: m["gamma_r"]), be=per(lambda m: m["beta"]),
        bulk=per(lambda m: m["rho"] * (m["vp"] ** 2 - 2 * m["vs"] ** 2)
                 + 2.0 * m["rho"] * m["vs"] ** 2 / 3.0),
        h_max=np.array([m["h_max"] for m in mats])[mat_id].astype(np.float64),
        dt=float(cfg["dt"]), omega0=float(cfg["omega0"]),
        g_min=float(cfg["g_min_frac"]), n_nodes=N,
    )


# ---------------------------------------------------------------------------
# element operators
# ---------------------------------------------------------------------------


def strain(t: Tables, u):
    """Voigt strain at every point ``[E*P, 6]`` from nodal ``u [N,3]``."""
    H = np.einsum("eni,epnj->epij", u[t.conn], t.g)
    eps = np.stack([H[..., 0, 0], H[..., 1, 1], H[..., 2, 2],
                    H[..., 0, 1] + H[..., 1, 0], H[..., 1, 2] + H[..., 2, 1],
                    H[..., 2, 0] + H[..., 0, 2]], axis=-1)
    return eps.reshape(-1, 6)


def strain_scale(t: Tables, u):
    """Size of the terms summed into each point's strain, ``[E*P]``: the
    scale of the rounding a program makes computing it."""
    ue = np.abs(u[t.conn]).max(axis=(1, 2))                       # [E]
    return (ue[:, None] * np.abs(t.g).sum(axis=(2, 3))).reshape(-1)


def nodal_force(t: Tables, s_pts, scale_e=None):
    """Σ_e Σ_p σ_ij(p) w(p) ∂N_n/∂x_j for point stresses ``[E*P, 6]``."""
    E = t.conn.shape[0]
    w = t.wdet if scale_e is None else t.wdet * scale_e[:, None]
    st = s_pts.reshape(E, NPOINT, 6)[..., VOIGT] * w[..., None, None]
    f = np.einsum("epij,epnj->eni", st, t.g)
    out = np.zeros((t.n_nodes, 3))
    for i in range(3):
        out[:, i] = np.bincount(t.conn.ravel(), f[..., i].ravel(), minlength=t.n_nodes)
    return out


def stiffness(t: Tables, D, x, scale_e):
    """Σ_e scale_e K_e(D) x, matrix-free; ``D [E*P,6,6]``."""
    eps = strain(t, x)
    return nodal_force(t, np.einsum("qab,qb->qa", D, eps), scale_e)


def damping(t: Tables, gmax):
    """(α, β_e) from the historic maximum strains ``[E*P, S]``."""
    frac = spring_frac(t, gmax)
    h = frac.reshape(-1, NPOINT).mean(1) * t.h_max
    return 2.0 * h.mean() * t.omega0, 2.0 * h / t.omega0


def spring_frac(t: Tables, gmax):
    x = (gmax / t.gr[:, None]) ** t.be[:, None]
    return (1.0 - 1.0 / (1.0 + x)).mean(1)


# ---------------------------------------------------------------------------
# multi-spring law, blockwise over points
# ---------------------------------------------------------------------------


def _bb(g, G0, gr, be):
    return G0 * g / (1.0 + (np.abs(g) / gr) ** be)


def _bbt(g, G0, gr, be):
    xb = (np.abs(g) / gr) ** be
    return G0 * (1.0 + (1.0 - be) * xb) / (1.0 + xb) ** 2


# A spring's branch decisions (loading direction, back on the backbone)
# compare two strains.  Where they differ by less than AMBIGUOUS times the
# size of what was summed into them, rounding decides, and either outcome
# is the law's; there the reference takes the program's.
AMBIGUOUS = 1e-5


def _law_block(t, sl, eps, st, rd, follow=None, scale=None):
    """Update the springs of points ``sl`` with total strain ``eps``; or,
    with ``eps`` None, evaluate σ_dev and D of the stored state.  With
    ``follow`` (the program's updated flags) decisions within rounding
    take the program's outcome."""
    G0, gr, be = t.G0[sl, None], t.gr[sl, None], t.be[sl, None]
    grev, trev = st["gamma_rev"], st["tau_rev"]
    gprev, gmax = st["gamma_prev"], st["gamma_max"]
    dirn, virg = st["direction"], st["virgin"]
    if eps is None:
        gamma, new = gprev, st
    else:
        gamma = rd(eps @ t.n.T)
        moving = np.sign(gamma - gprev).astype(np.int32)
        if follow is not None:
            band = AMBIGUOUS * (scale[:, None] + np.abs(gamma) + np.abs(gprev))
            amb_move = np.abs(gamma - gprev) <= band
            amb_join = np.abs(np.abs(gamma) - gmax) <= band
            moving = np.where(amb_move, follow["direction"], moving)
        tau_prev = np.where(virg == 1, _bb(gprev, G0, gr, be),
                            trev + 2.0 * _bb(0.5 * (gprev - grev), G0, gr, be))
        rev = (moving != 0) & (dirn != 0) & (moving != dirn)
        grev = np.where(rev, gprev, grev)
        trev = rd(np.where(rev, tau_prev, trev))
        dirn = np.where(moving != 0, moving, dirn)
        virg = np.where(rev, 0, virg)
        virg = np.where(np.abs(gamma) >= gmax, 1, virg)
        if follow is not None:
            virg = np.where(amb_join, follow["virgin"], virg)
        gmax = np.maximum(gmax, np.abs(gamma))
        new = {"gamma_rev": grev, "tau_rev": trev, "gamma_prev": gamma,
               "gamma_max": gmax, "direction": dirn, "virgin": virg}
    on = virg == 1
    tau = np.where(on, _bb(gamma, G0, gr, be),
                   trev + 2.0 * _bb(0.5 * (gamma - grev), G0, gr, be))
    gt = np.where(on, _bbt(gamma, G0, gr, be), _bbt(0.5 * (gamma - grev), G0, gr, be))
    gt = np.maximum(gt, t.g_min * G0)
    sdev = (tau * t.w) @ t.n
    Ddev = np.einsum("ps,sa,sb->pab", gt * t.w, t.n, t.n, optimize=True)
    return new, sdev, Ddev


def law(t: Tables, theta: dict, eps_pts=None, *, rd=None, follow=None,
        scale=None, block=16384, workers=8):
    """The law over all points: ``(θ', σ_dev [Q,6], D_dev [Q,6,6])``.

    With ``eps_pts`` the springs advance to that strain (θ' is new);
    without, σ_dev and D are those of the stored state ``theta``.
    ``follow``/``scale``: see :func:`_law_block`."""
    rd = rd or (lambda a: a)
    Q = theta["gamma_prev"].shape[0]
    sls = [slice(i, min(i + block, Q)) for i in range(0, Q, block)]

    def one(sl):
        st = {k: (theta[k][sl].astype(np.float64) if k in LEAVES
                  else theta[k][sl].astype(np.int32)) for k in LEAVES + FLAGS}
        e = None if eps_pts is None else eps_pts[sl]
        fo = None if follow is None else {k: np.asarray(follow[k][sl]) for k in FLAGS}
        return _law_block(t, sl, e, st, rd, fo, None if scale is None else scale[sl])

    with cf.ThreadPoolExecutor(workers) as ex:
        parts = list(ex.map(one, sls))
    new = {k: np.concatenate([p[0][k] for p in parts]) for k in LEAVES + FLAGS}
    return (new, np.concatenate([p[1] for p in parts]),
            np.concatenate([p[2] for p in parts]))


def add_bulk(t: Tables, eps_pts, sdev, Ddev):
    one = np.array([1.0, 1.0, 1.0, 0.0, 0.0, 0.0])
    vol = eps_pts[:, :3].sum(1)
    sig = sdev + t.bulk[:, None] * vol[:, None] * one
    D = Ddev + t.bulk[:, None, None] * np.outer(one, one)
    return sig, D


def constitutive(t: Tables, theta, u):
    """σ, D, q, α, β_e of a stored state (θ after its last update, u)."""
    _, sdev, Ddev = law(t, theta)
    eps = strain(t, u)
    sig, D = add_bulk(t, eps, sdev, Ddev)
    alpha, beta = damping(t, theta["gamma_max"].astype(np.float64))
    return {"D": D, "q": nodal_force(t, sig), "sig": sig, "alpha": alpha,
            "beta_e": beta}


def operators(t: Tables, D, alpha, beta):
    dt = t.dt
    diagA = (4.0 / dt**2 + 2.0 * alpha / dt) * t.mass[:, None] + (2.0 / dt) * t.dash
    A = lambda x: stiffness(t, D, x, 1.0 + (2.0 / dt) * beta) + diagA * x
    C = lambda v: alpha * t.mass[:, None] * v + stiffness(t, D, v, beta) + t.dash * v
    return A, C, diagA


def rhs(t, s0, c0, f_t, C):
    dt = t.dt
    return (t.force * f_t[None, :] - c0["q"] + C(s0["v"])
            + t.mass[:, None] * (s0["a"] + (4.0 / dt) * s0["v"]))


# ---------------------------------------------------------------------------
# the check and the control
# ---------------------------------------------------------------------------


def _rel(a, b):
    """‖a − b‖₂ / ‖b‖₂ (0 where both vanish)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    nb, nd = np.linalg.norm(b), np.linalg.norm(a - b)
    return 0.0 if nd == 0.0 else (nd / nb if nb > 0 else float("inf"))


def _rel_max(a, b, size):
    """max |a − b| over max ``size``: the sum's terms, whose rounding a
    program cannot avoid, set the scale of its gap."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    d, m = np.abs(a - b).max(), np.abs(size).max()
    return 0.0 if d == 0.0 else (d / m if m > 0 else float("inf"))


def _rel_q(t: Tables, q, ref):
    """Gap of an assembled internal force, over the norm of the sum of its
    terms' magnitudes (neighbouring elements' forces cancel at a node)."""
    size = nodal_force_abs(t, ref["sig"])
    return float(np.linalg.norm(np.asarray(q, np.float64) - ref["q"])
                 / np.linalg.norm(size))


def nodal_force_abs(t: Tables, s_pts):
    E = t.conn.shape[0]
    st = np.abs(s_pts.reshape(E, NPOINT, 6)[..., VOIGT]) * t.wdet[..., None, None]
    f = np.einsum("epij,epnj->eni", st, np.abs(t.g))
    out = np.zeros((t.n_nodes, 3))
    for i in range(3):
        out[:, i] = np.bincount(t.conn.ravel(), f[..., i].ravel(), minlength=t.n_nodes)
    return out


def f64(s):
    return {k: (np.asarray(v, np.float64) if k not in FLAGS else v)
            for k, v in s.items()}


def check_step(t: Tables, s0: dict, s1: dict, f_t, obs=None) -> dict:
    """How far one case's step ``s0 → s1`` is from the reference.

    ``s*`` hold ``u v a q [N,3]``, ``D [E*P,6,6]``, ``alpha``, ``beta_e
    [E]``, the spring leaves ``[E*P,S]``; ``s1`` also ``du`` (the solver's
    answer) and ``vel_obs`` (what the step reported at ``obs``).

    * ``residual``   ‖A δu − b‖/‖b‖ with A and b from the reference's own
                     σ, D, q and damping of ``s0``;
    * ``newmark``    widest gap of u', v', a' and the reported observations
                     from the recurrences, over the peak size of the terms
                     each recurrence sums;
    * ``theta``      largest relative L2 gap of a spring-history leaf from
                     the law applied to ``s0``'s springs at ``s1``'s strain;
    * ``flags``      share of spring flags that differ from the law's
                     (where rounding decides, the law takes the program's);
    * ``stress``     largest relative gap of D, q, α and β_e from the law,
                     for ``s0`` and for ``s1`` (q's over the size of the
                     element forces it sums).
    """
    s0, s1 = f64(s0), f64(s1)
    c0 = constitutive(t, s0, s0["u"])
    A, C, _ = operators(t, c0["D"], c0["alpha"], c0["beta_e"])
    du = s1["du"]
    b = rhs(t, s0, c0, np.asarray(f_t, np.float64), C)
    residual = _rel(A(du), b)
    dt = t.dt
    u1 = s0["u"] + du
    v1 = -s0["v"] + (2.0 / dt) * du
    a1 = -s0["a"] - (4.0 / dt) * s0["v"] + (4.0 / dt**2) * du
    au, av, adu = np.abs(s0["u"]), np.abs(s0["v"]), np.abs(du)
    newmark = max(_rel_max(s1["u"], u1, au + adu),
                  _rel_max(s1["v"], v1, av + (2.0 / dt) * adu),
                  _rel_max(s1["a"], a1, np.abs(s0["a"]) + (4.0 / dt) * av
                           + (4.0 / dt**2) * adu))
    if obs is not None:
        newmark = max(newmark, _rel_max(s1["vel_obs"], s1["v"][obs], s1["v"][obs]))
    theta_ref, sdev, Ddev = law(t, s0, strain(t, s1["u"]), follow=s1,
                                scale=strain_scale(t, s1["u"]))
    theta = max(_rel(s1[k], theta_ref[k]) for k in LEAVES)
    flags = max(float(np.mean(np.asarray(s1[k]) != theta_ref[k])) for k in FLAGS)
    sig1, D1 = add_bulk(t, strain(t, s1["u"]), sdev, Ddev)
    al1, be1 = damping(t, theta_ref["gamma_max"])
    stress = max(
        _rel(s0["D"], c0["D"]), _rel_q(t, s0["q"], c0),
        _rel(s0["beta_e"], c0["beta_e"]), _rel(s0["alpha"], c0["alpha"]),
        _rel(s1["D"], D1),
        _rel_q(t, s1["q"], {"q": nodal_force(t, sig1), "sig": sig1}),
        _rel(s1["beta_e"], be1), _rel(s1["alpha"], al1),
    )
    return {"residual": residual, "newmark": newmark, "theta": theta,
            "flags": flags, "stress": stress}


def bf16_round(a):
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def control_step(t: Tables, s0: dict, f_t, obs=None, *, rd=bf16_round,
                 tol=1e-6, maxiter=60) -> dict:
    """The reference computing the step itself in a lower precision: every
    stored value (state, tangent, solver vectors, springs) rounded by
    ``rd``, the solve by Jacobi-preconditioned CG.  Returns ``s1`` in the
    layout ``check_step`` reads."""
    s0 = {k: (rd(v) if k not in FLAGS else v) for k, v in f64(s0).items()}
    c0 = {k: rd(v) for k, v in constitutive(t, s0, s0["u"]).items()}
    A, C, diagA = operators(t, c0["D"], c0["alpha"], c0["beta_e"])
    A_r = lambda x: rd(A(x))
    b = rd(rhs(t, s0, c0, np.asarray(f_t, np.float64), C))
    M = 1.0 / diagA
    x = np.zeros_like(b)
    r = b.copy()
    z = rd(M * r)
    p = z
    rz = np.sum(r * z)
    bn = np.linalg.norm(b)
    for _ in range(maxiter):
        if np.linalg.norm(r) / bn <= tol:
            break
        Ap = A_r(p)
        al = rz / np.sum(p * Ap)
        x, r = rd(x + al * p), rd(r - al * Ap)
        z = rd(M * r)
        rz_new = np.sum(r * z)
        p = rd(z + (rz_new / rz) * p)
        rz = rz_new
    du = x
    dt = t.dt
    u1 = rd(s0["u"] + du)
    v1 = rd(-s0["v"] + (2.0 / dt) * du)
    a1 = rd(-s0["a"] - (4.0 / dt) * s0["v"] + (4.0 / dt**2) * du)
    th1, sdev, Ddev = law(t, s0, strain(t, u1), rd=rd)
    sig1, D1 = add_bulk(t, strain(t, u1), sdev, Ddev)
    al1, be1 = damping(t, th1["gamma_max"])
    out = {k: (rd(v) if k in LEAVES else v) for k, v in th1.items()}
    out.update(u=u1, v=v1, a=a1, du=du, D=rd(D1), q=rd(nodal_force(t, sig1)),
               alpha=rd(al1), beta_e=rd(be1))
    if obs is not None:
        out["vel_obs"] = v1[obs]
    return out
