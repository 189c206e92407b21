"""What a driver needs from the program: the mesh and the solver settings,
both as the configuration file states them."""
from __future__ import annotations

import numpy as np


def make_mesh(cfg: dict):
    from repro.fem import meshgen

    m = cfg["mesh"]
    mats = [meshgen.Material(**{k: v for k, v in mat.items() if k != "name"})
            for mat in cfg["materials"]]
    mesh = meshgen.generate(m["nx"], m["ny"], m["nz"], lx=m["lx"], ly=m["ly"],
                            lz=m["lz"], materials=mats,
                            pad_elems_to=m["pad_elems_to"])
    got = {"n_elem": mesh.n_elem, "n_nodes": mesh.n_nodes, "npad": mesh.npad}
    want = {k: cfg[k] for k in ("n_elem", "n_nodes")} | {"npad": 0}
    if got != want:
        raise SystemExit(f"mesh {got} differs from the configuration's {want}")
    return mesh


def sim_config(cfg: dict):
    import jax.numpy as jnp

    from repro.fem import methods

    if cfg["dtype"] != "float32":
        raise SystemExit(f"unsupported dtype {cfg['dtype']!r}")
    # npart: the blocks a streamed spring state is cut into; a resident
    # configuration has none and leaves it at the program's default
    streamed = {"npart": cfg["npart"]} if "npart" in cfg else {}
    return methods.SeismicConfig(
        **streamed, dt=cfg["dt"], tol=cfg["tol"], maxiter=cfg["maxiter"],
        nspring=cfg["nspring"], schedule=cfg["schedule"],
        inner_iters=cfg["inner_iters"], omega0=cfg["omega0"],
        dtype=jnp.float32, backend=cfg["kernel_backend"],
        tile_e=cfg["tile_e"], tile_p=cfg["tile_p"],
        warm_start=cfg["warm_start"], precond_every=cfg["precond_every"],
        health=cfg["health"],
    )


def observed_nodes(mesh) -> np.ndarray:
    """The launcher's observation point: the middle surface node."""
    s = mesh.surface
    return np.asarray(s[len(s) // 2: len(s) // 2 + 1])


def unflat(x) -> np.ndarray:
    """The solvers' component-major flat vector → ``[N,3]``."""
    return np.asarray(x).reshape(3, -1).T
