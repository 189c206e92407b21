"""FEM substrate: mesh invariants, operator equivalences, solver convergence,
and the headline integration test — all four of the paper's methods advance
identical physics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.fem import assembly, meshgen, methods, multispring as ms, quadrature as quad, solver, spmv


@pytest.fixture(scope="module")
def x64():
    with jax.enable_x64(True):
        yield


@pytest.fixture(scope="module")
def mesh():
    return meshgen.generate(3, 3, 3, pad_elems_to=8)


@pytest.fixture(scope="module")
def elastic(mesh, x64):
    """Elastic tangent D0 at every Gauss point + spring machinery."""
    params = ms.material_params_for_mesh(mesh)
    n, w = ms.spring_directions(30)
    n_j, w_j = jnp.asarray(n), jnp.asarray(w)
    springs = ms.init_state(mesh.n_elem * quad.NPOINT, 30)
    eps0 = jnp.zeros((mesh.n_elem * quad.NPOINT, 6))
    sig0, D0, _ = ms.update(eps0, springs, params, n_j, w_j)
    return params, D0.reshape(mesh.n_elem, quad.NPOINT, 6, 6), sig0


# ---------------------------------------------------------------------------
# mesh / quadrature invariants
# ---------------------------------------------------------------------------


@given(n=st.integers(1, 3))
@settings(max_examples=3, deadline=None)
def test_mesh_invariants(n):
    m = meshgen.generate(n, n, n, lx=100.0, ly=100.0, lz=50.0, pad_elems_to=4)
    assert (m.detJ > 0).all()
    assert (m.mass > 0).all()
    np.testing.assert_allclose(m.wdet.sum(), 100.0 * 100.0 * 50.0, rtol=1e-9)
    assert m.n_elem % 4 == 0
    # BCSR structure is a valid symmetric-pattern CSR
    assert m.row_ptr[-1] == len(m.col_idx)
    assert (np.diff(m.row_ptr) > 0).all()
    # every element's (i,i) entry maps to that node's diagonal slot
    E0 = m.n_elem - m.npad
    for e in (0, E0 // 2):
        for a in range(10):
            assert m.entry_map[e, a, a] == m.diag_slots[m.conn[e, a]]


def test_shape_functions_partition_of_unity():
    pts = np.random.default_rng(0).dirichlet(np.ones(4), size=16)
    N = quad.shape_functions(pts)
    np.testing.assert_allclose(N.sum(axis=1), 1.0, atol=1e-12)
    g = quad.shape_gradients_ref(pts)
    np.testing.assert_allclose(g.sum(axis=1), 0.0, atol=1e-12)


# ---------------------------------------------------------------------------
# operator equivalence: dense == BCSR == EBE
# ---------------------------------------------------------------------------


def test_matvec_equivalence(mesh, elastic, x64):
    _, D0, _ = elastic
    Jinv, wdet = jnp.asarray(mesh.Jinv), jnp.asarray(mesh.wdet)
    K_e = assembly.element_stiffness(D0, Jinv, wdet)
    vals = assembly.assemble_bcsr(
        assembly.element_blocks(D0, Jinv, wdet), mesh.entry_map, len(mesh.col_idx))
    A = assembly.dense_assemble(K_e, mesh.elem_dofs, mesh.ndof)
    x = jax.random.normal(jax.random.key(0), (mesh.n_nodes, 3))
    y_dense = (A @ x.reshape(-1)).reshape(-1, 3)
    y_crs = spmv.bcsr_matvec(vals, mesh.rowids, mesh.col_idx, x)
    y_ebe = spmv.ebe_matvec(x, D0, mesh)
    scale = float(jnp.abs(y_dense).max())
    np.testing.assert_allclose(np.asarray(y_crs), np.asarray(y_dense), atol=1e-9 * scale)
    np.testing.assert_allclose(np.asarray(y_ebe), np.asarray(y_dense), atol=1e-9 * scale)


def test_stiffness_symmetric_psd_rigid(mesh, elastic, x64):
    _, D0, _ = elastic
    K_e = assembly.element_stiffness(D0, jnp.asarray(mesh.Jinv), jnp.asarray(mesh.wdet))
    asym = jnp.abs(K_e - jnp.swapaxes(K_e, -1, -2)).max() / jnp.abs(K_e).max()
    assert float(asym) < 1e-12
    # rigid translations are in the null space
    t = jnp.tile(jnp.array([1.0, -2.0, 0.5]), (mesh.n_nodes, 1))
    resid = jnp.abs(spmv.ebe_matvec(t, D0, mesh)).max() / jnp.abs(K_e).max()
    assert float(resid) < 1e-10


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_ebe_equals_crs_property(seed):
    """Property: EBE and BCSR agree for random tangents D (sym PSD) and x."""
    m = meshgen.generate(2, 2, 2, pad_elems_to=4)
    rng = np.random.default_rng(seed)
    Q = rng.normal(size=(m.n_elem, quad.NPOINT, 6, 6))
    D = jnp.asarray(Q @ Q.transpose(0, 1, 3, 2) + 6 * np.eye(6))
    x = jnp.asarray(rng.normal(size=(m.n_nodes, 3)))
    Kb = assembly.element_blocks(D, jnp.asarray(m.Jinv), jnp.asarray(m.wdet))
    vals = assembly.assemble_bcsr(Kb, m.entry_map, len(m.col_idx))
    y_crs = spmv.bcsr_matvec(vals, m.rowids, m.col_idx, x)
    y_ebe = spmv.ebe_matvec(x, D, m)
    np.testing.assert_allclose(
        np.asarray(y_ebe), np.asarray(y_crs), rtol=1e-5, atol=1e-6 * float(jnp.abs(y_crs).max())
    )


# ---------------------------------------------------------------------------
# solvers
# ---------------------------------------------------------------------------


def _spd_system(mesh, elastic):
    _, D0, _ = elastic
    Kb = assembly.element_blocks(D0, jnp.asarray(mesh.Jinv), jnp.asarray(mesh.wdet))
    vals = assembly.assemble_bcsr(Kb, mesh.entry_map, len(mesh.col_idx))
    diag_add = jnp.asarray(mesh.mass)[:, None] * 1e4  # mass term → SPD
    vals = assembly.add_diag(vals, mesh.diag_slots, diag_add)
    Minv = assembly.block_jacobi_inverse(vals, mesh.diag_slots)

    def mv(xf):  # dtype-follows-input (serves the fp32 inner solve too)
        return solver.flat(spmv.bcsr_matvec(
            vals.astype(xf.dtype), mesh.rowids, mesh.col_idx, solver.unflat(xf)))

    return mv, Minv


def test_pcg_converges(mesh, elastic, x64):
    mv, Minv = _spd_system(mesh, elastic)
    b = jax.random.normal(jax.random.key(1), (mesh.ndof,))
    res = solver.pcg(mv, b, solver.block_jacobi_apply(Minv), tol=1e-8, maxiter=2000)
    assert float(res.relres) <= 1e-8
    r = b - mv(res.x)
    assert float(jnp.linalg.norm(r) / jnp.linalg.norm(b)) <= 1e-7


def test_fcg_with_inner_preconditioner(mesh, elastic, x64):
    mv, Minv = _spd_system(mesh, elastic)
    inner = solver.make_inner_pcg_preconditioner(
        mv, solver.block_jacobi_apply(Minv.astype(jnp.float32)), inner_iters=6
    )
    b = jax.random.normal(jax.random.key(2), (mesh.ndof,))
    res_plain = solver.pcg(mv, b, solver.block_jacobi_apply(Minv), tol=1e-8, maxiter=2000)
    res_fcg = solver.fcg(mv, b, inner, tol=1e-8, maxiter=2000)
    assert float(res_fcg.relres) <= 1e-8
    # inner-preconditioned solver must reduce outer iterations (paper's claim)
    assert int(res_fcg.iters) < int(res_plain.iters)


# ---------------------------------------------------------------------------
# the paper's four methods advance the same physics
# ---------------------------------------------------------------------------


def test_four_methods_agree(mesh, x64):
    cfg = methods.SeismicConfig(dt=0.01, tol=1e-8, maxiter=600, npart=4, nspring=12)
    nt = 6
    t = np.arange(nt) * cfg.dt
    wave = np.zeros((nt, 3))
    wave[:, 0] = 0.3 * np.sin(2 * np.pi * 2.0 * t)
    wave[:, 2] = 0.1 * np.sin(2 * np.pi * 1.5 * t)

    outs = {}
    for m in methods.METHODS:
        outs[m] = methods.run(mesh, cfg, wave, method=m, observe=mesh.surface[:2])
        assert np.isfinite(np.asarray(outs[m]["velocity_history"])).all()
        assert float(outs[m]["relres"][1:].max()) <= cfg.tol

    ref = np.asarray(outs["baseline1"]["velocity_history"])
    assert np.abs(ref).max() > 0  # something actually happened
    for m in ("baseline2", "proposed1"):
        np.testing.assert_allclose(
            np.asarray(outs[m]["velocity_history"]), ref, rtol=0, atol=1e-12 * np.abs(ref).max()
        )
    # EBE + fp32 inner preconditioner: same physics within mixed-precision tol
    np.testing.assert_allclose(
        np.asarray(outs["proposed2"]["velocity_history"]), ref,
        atol=1e-5 * np.abs(ref).max(),
    )


def test_nonlinearity_engages(mesh, x64):
    """Strong input must degrade the tangent (springs yield) and add damping."""
    cfg = methods.SeismicConfig(dt=0.01, tol=1e-7, maxiter=600, npart=2, nspring=12)
    ops = methods.FemOperators(mesh, cfg)
    carry = methods.initial_carry(ops)
    step = methods.make_step("baseline1", ops)[0]
    nt = 8
    wave = np.zeros((nt, 3))
    wave[:, 0] = 5.0  # strong static-ish push
    D0 = np.asarray(carry[2]).copy()
    for k in range(nt):
        carry, aux = step(carry, jnp.asarray(wave[k]))
    D_end = np.asarray(carry[2])
    alpha_end = float(carry[3])
    # tangent shear stiffness must drop somewhere
    assert D_end[..., 3, 3].min() < 0.99 * D0[..., 3, 3].max()
    assert alpha_end > 0.0
