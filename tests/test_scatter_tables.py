"""Gather-form nodal assembly (``spmv.scatter_nodal`` on
``meshgen.scatter_tables``) against a float64 ``np.add.at`` reference, and
the tables' invariants."""
import jax
import numpy as np
import pytest

from repro.fem import meshgen, quadrature as quad, spmv

EPS32 = float(np.finfo(np.float32).eps)


def _add_at(fT: np.ndarray, conn: np.ndarray, n_nodes: int) -> tuple[np.ndarray, np.ndarray]:
    """float64 Σ per node of ``fT [30, E]`` (row 3n+a), and Σ|·| for the
    rounding bound."""
    E = conn.shape[0]
    f = np.asarray(fT, np.float64).reshape(quad.NNODE, 3, E)
    out, mag = np.zeros((n_nodes, 3)), np.zeros((n_nodes, 3))
    for n in range(quad.NNODE):
        np.add.at(out, conn[:, n], f[n].T)
        np.add.at(mag, conn[:, n], np.abs(f[n].T))
    return out, mag


def _check(y, fT, conn, n_nodes):
    ref, mag = _add_at(fT, conn, n_nodes)
    valence = np.bincount(conn.ravel(), minlength=n_nodes)[:, None]
    # fp32 summation of `valence` terms in any order: |err| ≤ valence·ε·Σ|f|
    bound = valence * EPS32 * mag + np.finfo(np.float32).tiny
    err = np.abs(np.asarray(y, np.float64) - ref)
    assert (err <= bound).all(), float((err / bound).max())


MESHES = {
    "2x2x2": dict(nx=2, ny=2, nz=2),
    "3x3x4": dict(nx=3, ny=3, nz=4),
    "4x4x4": dict(nx=4, ny=4, nz=4),
    "3x3x3-ghosts": dict(nx=3, ny=3, nz=3, pad_elems_to=8),
}


@pytest.fixture(scope="module", params=sorted(MESHES))
def mesh(request):
    return meshgen.generate(**MESHES[request.param])


def _synthetic(seed=0, E=400, N=300):
    """Random connectivity: valences spread over many distinct values."""
    rng = np.random.default_rng(seed)
    conn = rng.integers(0, N, size=(E, quad.NNODE)).astype(np.int32)
    conn[: E // 8, :3] = 7  # one node of much higher valence
    return conn, N


def test_scatter_nodal_matches_add_at(mesh):
    fT = np.random.default_rng(1).standard_normal((quad.NDOF, mesh.n_elem)).astype(np.float32)
    if mesh.npad:
        fT[:, -mesh.npad:] = np.random.default_rng(2).standard_normal(
            (quad.NDOF, mesh.npad)).astype(np.float32)  # ghost slots land on node 0
        assert (mesh.conn[-mesh.npad:] == 0).all()
    y = jax.jit(lambda f: spmv.scatter_nodal(f, mesh.scatter))(fT)
    assert y.shape == (mesh.n_nodes, 3) and y.dtype == np.float32
    _check(y, fT, mesh.conn, mesh.n_nodes)


def test_scatter_nodal_under_kset_vmap(mesh):
    fT = np.random.default_rng(3).standard_normal(
        (2, quad.NDOF, mesh.n_elem)).astype(np.float32)
    fn = jax.jit(jax.vmap(lambda f: spmv.scatter_nodal(f, mesh.scatter)))
    y = fn(fT)
    for k in range(2):
        _check(y[k], fT[k], mesh.conn, mesh.n_nodes)
    np.testing.assert_array_equal(y, fn(fT))  # a fixed summation order


def test_more_valences_than_buckets_merge_and_pad():
    conn, N = _synthetic()
    assert len(np.unique(np.bincount(conn.ravel(), minlength=N))) > meshgen.MAX_BUCKETS
    t = meshgen.scatter_tables(conn, N)
    assert t.n_buckets == meshgen.MAX_BUCKETS
    assert t.padded and t.pad_share > 0.0
    _assert_tables_cover(t, conn, N)
    fT = np.random.default_rng(4).standard_normal((quad.NDOF, conn.shape[0])).astype(np.float32)
    _check(jax.jit(lambda f: spmv.scatter_nodal(f, t))(fT), fT, conn, N)


def _assert_tables_cover(t, conn, n_nodes):
    E = conn.shape[0]
    pad = quad.NDOF * E
    sizes = [s.shape[2] for s in t.slots]
    assert sum(sizes) == n_nodes and len(t.slots) == t.n_buckets
    # order is a permutation of the nodes
    np.testing.assert_array_equal(np.sort(t.order), np.arange(n_nodes))
    bucket_node = np.empty(sum(sizes), np.int64)
    bucket_node[t.order] = np.arange(n_nodes)
    real, n_pad, col = [], 0, 0
    for s in t.slots:
        assert s.dtype == np.int32 and s.shape[0] == 3
        for a in range(3):
            keep = s[a] != pad
            n_pad += int((~keep).sum()) if a == 0 else 0
            rows = s[a] // E
            assert (rows[keep] % 3 == a).all()
            # each real position belongs to the column's node
            nodes = np.broadcast_to(bucket_node[col:col + s.shape[2]], s[a].shape)
            np.testing.assert_array_equal(conn[s[a][keep] % E, rows[keep] // 3], nodes[keep])
            real.append(s[a][keep])
        col += s.shape[2]
    # every slot of every component appears exactly once over all buckets
    np.testing.assert_array_equal(np.sort(np.concatenate(real)), np.arange(pad))
    assert t.pad_share == n_pad / (quad.NNODE * E)


def test_tables_cover_every_slot_once(mesh):
    _assert_tables_cover(mesh.scatter, mesh.conn, mesh.n_nodes)
    assert mesh.scatter.n_buckets <= meshgen.MAX_BUCKETS


def test_basin22_has_eight_valences_and_no_padding():
    """The ``ebe-k2-resident`` mesh: one bucket per valence, zero padding."""
    m = meshgen.generate(22, 22, 22, pad_elems_to=8)
    assert (m.n_elem, m.n_nodes, m.npad) == (63_888, 91_125, 0)
    t = m.scatter
    assert t.n_buckets == 8 and t.pad_share == 0.0 and not t.padded
    assert [s.shape[1:] for s in t.slots] == [
        (1, 132), (2, 3_042), (3, 5_544), (4, 30_618), (6, 39_756), (8, 126),
        (12, 2_646), (24, 9_261)]
    _assert_tables_cover(t, m.conn, m.n_nodes)
