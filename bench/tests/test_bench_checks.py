"""The comparison that decides ``correct``, on the CPU at a small mesh.

A sound run passes; the lower-precision control and each fault the cells
can have (a step that leaves its state unchanged, half the batch left out,
the solver's answer altered by 1% where it is produced) fail.  The cells' own
limits are used as they stand."""
import copy
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402

SEED = 2**31 + 977


def small(c, n=4):
    c = copy.deepcopy(c)
    cfg = c["config"]
    cfg["mesh"].update(nx=n, ny=n, nz=n)
    cfg["n_elem"], cfg["n_nodes"] = 6 * n**3, (2 * n + 1) ** 3
    return c


def run_small(cell, fault=None):
    return run.run_cell(cell, SEED, 0.5, False, require_tpu=False,
                        cell_override=small, fault=fault)


CELLS = ["ebe-k2-resident", "crs-hoststream", "crs-resident"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run_small(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0


def _kept(carry):
    """A copy of the carry that outlives a call which takes over its
    buffers (a donated carry)."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.copy(x), x.sharding), carry)


def _unchanged(drv):
    fn = drv.fn

    def frozen(carry, *a):
        old = _kept(carry)
        _, outs = fn(carry, *a)
        return old, outs

    drv.fn = frozen


def _half_batch(drv):
    """kset cells: only the first half of the cases advance; one-case
    cells: only the first half of the spring-state blocks are written."""
    import jax

    fn = drv.fn

    def half(carry, *a):
        old = _kept(carry)
        new, outs = fn(carry, *a)
        if drv.cases > 1:
            h = drv.cases // 2
            new = jax.tree_util.tree_map(
                lambda n_, o: n_.at[h:].set(o[h:]), new, old)
        else:
            ps = new[1]
            k = len(ps.blocks) // 2
            blocks = ps.blocks[:k] + old[1].blocks[k:]
            new = (new[0], type(ps)(blocks=blocks, spec=ps.spec), *new[2:])
        return new, outs

    drv.fn = half


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered_answer"])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    if fault == "altered_answer":
        from repro.fem import solver

        for name in ("pcg", "fcg"):
            orig = getattr(solver, name)

            def wrong(*a, _orig=orig, **k):
                r = _orig(*a, **k)
                return r._replace(x=r.x * (1.0 + 1e-2))

            monkeypatch.setattr(solver, name, wrong)
        hook = None
    else:
        hook = {"unchanged": _unchanged, "half_batch": _half_batch}[fault]
    out = run_small(cell, hook)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["ebe-k2-resident", "crs-resident"])
def test_lower_precision_control_is_not_correct(cell):
    """The reference in the program's place, every stored value in
    bfloat16: the step the check must refuse."""
    import importlib

    from harness import checks, program, traffic
    from reference import fem_ref

    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    c = small(run.load_cell(cell))
    cfg = c["config"]
    waves, _ = traffic.window_waves(c["traffic"], cfg["cases"], cfg["dt"],
                                    cfg["record_steps"], SEED)
    mesh = program.make_mesh(cfg)
    drv = importlib.import_module(f"drivers.{c['cell']['driver']}").Driver(
        cfg, c["cell"], mesh, waves)
    for _ in range(4):
        drv.call()
    s0 = drv.snapshot()
    f_t = waves[:, drv.t]
    obs = program.observed_nodes(mesh)
    t = fem_ref.build_tables(cfg, mesh.coords, mesh.conn, mesh.mat_id)
    s1 = [fem_ref.control_step(t, s, f_t[i], obs) for i, s in enumerate(s0)]
    numbers = checks.compare(cfg, mesh.coords, mesh.conn, mesh.mat_id, s0, s1,
                             f_t, obs, [1], np.zeros(len(s0)), 0)
    ok, lines = checks.judge(numbers, c["cell"]["limits"])
    assert not ok, lines
    assert numbers["residual"] > c["cell"]["limits"]["residual"]


@pytest.mark.parametrize("cell", ["ebe-k2-resident", "crs-resident"])
def test_seeds_change_inputs_not_work(cell):
    """The traffic's records are fixed; a seed assigns them to the case
    slots and flips their signs.  The law and the solver are exactly odd in
    the input: the same iterations, the negated response, to the bit."""
    import importlib

    from harness import program, traffic

    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    c = small(run.load_cell(cell))
    cfg = c["config"]
    draw = lambda s: traffic.window_waves(c["traffic"], cfg["cases"], cfg["dt"],
                                          cfg["record_steps"], s)[0]
    waves = draw(SEED)
    other = next(w for w in map(draw, range(1, 50)) if not np.array_equal(w, waves))
    mesh = program.make_mesh(cfg)
    Driver = importlib.import_module(f"drivers.{c['cell']['driver']}").Driver
    runs = []
    for w in (waves, other):
        d = Driver(cfg, c["cell"], mesh, w)
        runs.append(([d.call().tolist() for _ in range(6)], d.snapshot()))
    (it_a, sa), (it_b, sb) = runs
    assert it_a == it_b
    assert sorted(np.abs(waves[:, 3, 0])) == sorted(np.abs(other[:, 3, 0]))
    for a in sa:  # each case of one run is a case of the other, maybe negated
        assert any(np.array_equal(a["u"], s * b["u"]) for b in sb for s in (1, -1))
