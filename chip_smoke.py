#!/usr/bin/env python3
"""Bring-up check: the ensemble-FEM main path on one TPU chip.

One process runs every phase (a process that has touched JAX holds the
chip, so nothing here spawns a JAX child):

  (a) device       a TPU is present; both FEM kernels resolve to compiled
                   Pallas; prints the device kind, memory kinds and dtype
  (b) correctness  a 4×4×4-hex campaign (150 springs, 2 cases, 8 steps)
                   with the Pallas kernels against the jnp oracle and
                   against the host CPU (required), and ``proposed1``
                   (CRS-PCG) on the chip against the host CPU; every
                   health word clean, every solve converged
  (d) streamed θ   ``methods.run(method="proposed1", offload=True)`` at
                   22×22×24 hexes (69,696 tet10): θ ≥ 1 GB streamed from
                   pinned host memory in 8 blocks, against the resident
                   ``proposed1`` and ``proposed2`` runs of the same wave
  (c) campaign     ``repro.launch.campaign.main`` at real size (22×22×22
                   hexes = 63,888 tet10 elements, 150 springs, kset 2,
                   16 steps), checkpointed; the shards' responses at the
                   launcher's observed surface node finite and non-zero
  (e) surrogate    ``fit_shards`` for a few steps on (c)'s shards

``--chips 4`` runs only the case-sharded campaign on a 4-device case mesh
and the same cases on one device of that host, and compares them.

Velocity histories of every node are compared relative to the larger
peak of the two runs.  Every solve stops at relative residual
``tol = 1e-6`` (``surrogate.dataset.simulation_config``), in fp32.

* ``TOL = 1e-4`` — the same solver with different arithmetic (Pallas
  kernels vs jnp, one device vs a shard_map): the runs differ by rounding
  fed through ~tol-accurate solves over 8 steps.  On the CPU, Pallas
  (interpret mode) vs jnp gives 2.5e-7.
* ``TOL_EXACT = 1e-6`` — the same program with θ streamed from host memory
  or resident on the device: only the placement differs (bit-identical on
  the CPU).
* ``TOL_SOLVERS = 1e-3`` — ``proposed1`` (CRS-PCG: K and the far larger
  mass/dt² term summed into one fp32 matrix) against ``proposed2``
  (EBE-FCG: applied separately).  Their gap is the two solvers' different
  fp32 attainable accuracy, not the stopping test: on the CPU at 12×12×12
  it is 1.5e-5 at tol 1e-6 and grows to 1.0e-4 when tol tightens to 1e-7,
  and it grows with the mesh's condition number.

The last line of stdout is ``{"ok": true, "device": {...}}`` only when
every phase passed; otherwise the script exits non-zero and prints no such
line.  Without an accelerator (e.g. ``JAX_PLATFORMS=cpu``) it exits
non-zero after phase (a): it never reports a CPU run.

    python chip_smoke.py [--chips 4] [--out DIR]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TOL = 1e-4  # max |Δv| / peak |v|: same solver, different arithmetic
TOL_EXACT = 1e-6  # same solver and arithmetic, θ in another memory
TOL_SOLVERS = 1e-3  # CRS-PCG against EBE-FCG (see the module docstring)
SOLVER_TOL = 1e-6  # CG relative residual of simulation_config
STATE_BYTES = 24  # fp32 θ per spring: 4 float32 + 2 int32 history words
# the largest cube whose kset-2 round program fits one v5e by its compile
# for a described chip: 22³ 14.45 GB, 24³ 18.74 GB (memory_analysis)
CAMPAIGN_MESH = (22, 22, 22)  # hexes: 63,888 tet10, θ 0.92 GB per case
CAMPAIGN_STEPS = 16
STREAM_MESH = (22, 22, 24)    # hexes: 69,696 tet10, θ 1.004 GB
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums the backend-compile seconds JAX reports (cache hits compile
    nothing and add nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]

    def mark(self):
        return self.seconds, self.count


def agree(name, a, b, tol=None):
    """Max |a − b| over the larger peak, checked against ``tol`` (TOL)."""
    import numpy as np

    tol = TOL if tol is None else tol
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    peak = max(float(np.abs(a).max()), float(np.abs(b).max()))
    diff = float(np.abs(a - b).max())
    rel = diff / peak if peak > 0 else float("inf")
    log(f"  {name}: max|Δv| = {diff!r} m/s, peak |v| = {peak!r} m/s, "
        f"relative {rel!r} (tolerance {tol})")
    if not (rel <= tol):
        raise AssertionError(f"{name}: relative disagreement {rel} > {tol}")


def check_campaign_health(name, res):
    import numpy as np

    log(f"  {name}: health words {res.health.tolist()}, non-converged "
        f"solves {res.nonconverged.tolist()}, mean CG iterations "
        f"{float(np.mean(res.iters))!r}")
    if res.health.size == 0 or np.any(res.health) or np.any(res.nonconverged):
        raise AssertionError(f"{name}: unhealthy or non-converged cases")
    if not np.all(np.isfinite(res.velocity_history)):
        raise AssertionError(f"{name}: non-finite velocities")


def converged(name, res, tol):
    import numpy as np

    relres = np.asarray(res["relres"])
    log(f"  {name}: CG iterations {np.asarray(res['iters']).tolist()}, max "
        f"relres {float(relres.max())!r}")
    if not np.all(relres <= tol):
        raise AssertionError(f"{name}: a solve did not converge")


def theta_bytes(n_elem, nspring):
    from repro.fem import quadrature as quad

    return n_elem * quad.NPOINT * nspring * STATE_BYTES


def small_campaign_inputs(mesh_n, n_waves, seed):
    """Inputs of a campaign that observes every node: in 8 steps the wave
    has not reached the surface yet, so only the whole field carries a
    response that the solver tolerance bounds relative to its peak."""
    import numpy as np

    from repro.fem import meshgen
    from repro.surrogate.dataset import EnsembleConfig, random_band_limited_waves

    ec = EnsembleConfig(n_waves=n_waves, nt=8, mesh_n=mesh_n, nspring=150,
                        seed=seed, kset=2)
    mesh = meshgen.generate(*mesh_n, pad_elems_to=8)
    return ec, mesh, random_band_limited_waves(ec), np.arange(mesh.n_nodes)


def run_small_campaign(ec, mesh, waves, obs, backend, device_mesh=None):
    from repro.campaign import CampaignConfig, run_campaign
    from repro.surrogate.dataset import simulation_config

    sim = simulation_config(ec, backend=backend, warm_start=True, health=True)
    return run_campaign(
        mesh, sim, waves, observe=obs, device_mesh=device_mesh,
        campaign=CampaignConfig(kset=ec.kset, method="proposed2", seed=ec.seed),
    )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(jax, want_chips):
    import jax.numpy as jnp

    from repro.fem import backend as fem_backend, methods

    devs = jax.devices()
    d = devs[0]
    log(f"  platform={d.platform} device_kind={d.device_kind!r} "
        f"count={len(devs)} memory kinds="
        f"{[m.kind for m in d.addressable_memories()]} "
        f"dtype={jnp.zeros(()).dtype} x64={jax.config.jax_enable_x64}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {d.platform!r}; "
                         f"this check never reports a run elsewhere")
    if len(devs) < want_chips:
        raise SystemExit(f"--chips {want_chips} but JAX sees {len(devs)} "
                         f"device(s)")
    kb = fem_backend.resolve(methods.SeismicConfig(backend="auto"))
    log(f"  kernel backend (auto): {kb.describe()}")
    if (kb.ebe, kb.multispring) != ("pallas", "pallas"):
        raise AssertionError(f"kernels did not resolve to compiled Pallas: "
                             f"{kb.describe()}")


def phase_correctness(jax, seed):
    """Pallas and the jnp oracle share their jnp glue on the chip, so the
    host CPU (independent arithmetic) is a required witness: a run whose JAX
    has no cpu backend fails here."""
    import jax.numpy as jnp

    from repro.fem import methods

    ec, mesh, waves, obs = small_campaign_inputs((4, 4, 4), 2, seed)
    log(f"  mesh 4x4x4: {mesh.n_elem} elements, {mesh.n_nodes} nodes, "
        f"nspring 150, 2 cases × {ec.nt} steps, kset 2")
    cpu = jax.devices("cpu")[0]  # RuntimeError (phase fails) without one
    pal = run_small_campaign(ec, mesh, waves, obs, "auto")
    ref = run_small_campaign(ec, mesh, waves, obs, "jnp")
    with jax.default_device(cpu):
        host = run_small_campaign(ec, mesh, waves, obs, "jnp")
    check_campaign_health("pallas", pal)
    check_campaign_health("jnp", ref)
    check_campaign_health("jnp (host CPU)", host)
    agree("pallas vs jnp (chip)", pal.velocity_history, ref.velocity_history)
    agree("pallas (chip) vs jnp (host CPU)", pal.velocity_history,
          host.velocity_history)

    # proposed1's CRS glue (element_blocks, BCSR assembly and matvec) against
    # the host CPU at TOL, tighter than (d)'s cross-solver bound
    cfg = methods.SeismicConfig(dt=ec.dt, tol=SOLVER_TOL, maxiter=400,
                                npart=2, nspring=150, dtype=jnp.float32)
    chip1 = methods.run(mesh, cfg, waves[0], method="proposed1", offload=False,
                        observe=obs)
    converged("proposed1 (chip)", chip1, cfg.tol)
    with jax.default_device(cpu):
        host1 = methods.run(mesh, dataclasses.replace(cfg, backend="jnp"),
                            waves[0], method="proposed1", offload=False,
                            observe=obs)
    converged("proposed1 (host CPU)", host1, cfg.tol)
    agree("proposed1 chip vs host CPU", chip1["velocity_history"],
          host1["velocity_history"])


def phase_streamed(jax, mesh_n, seed):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import hetmem
    from repro.fem import meshgen, methods
    from repro.surrogate.dataset import EnsembleConfig, random_band_limited_waves

    mesh = meshgen.generate(*mesh_n, pad_elems_to=8)
    cfg = methods.SeismicConfig(dt=0.01, tol=SOLVER_TOL, maxiter=400, npart=8,
                                nspring=150, dtype=jnp.float32, warm_start=True)
    tb = theta_bytes(mesh.n_elem, cfg.nspring)
    log(f"  mesh {'x'.join(map(str, mesh_n))}: {mesh.n_elem} elements, "
        f"θ = {tb} bytes ({tb / 1e9:.3f} GB) in {cfg.npart} blocks")
    if tb < 1e9:
        raise AssertionError("streamed θ must be at least 1 GB")
    ec = EnsembleConfig(n_waves=1, nt=8, mesh_n=mesh_n, nspring=150, seed=seed)
    wave = random_band_limited_waves(ec)[0]
    obs = np.arange(mesh.n_nodes)
    if not (hetmem.transfer_is_real(hetmem.HOST) and hetmem.outputs_can_pin_host()):
        raise AssertionError("runtime cannot stage pinned_host transfers")

    streamed = methods.run(mesh, cfg, wave, method="proposed1", offload=True,
                           observe=obs)
    jax.block_until_ready(streamed["velocity_history"])
    stats = jax.devices()[0].memory_stats() or {}
    kinds = sorted({leaf.sharding.memory_kind for leaf in
                    jax.tree_util.tree_leaves(streamed["springs"])})
    held = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(streamed["springs"]))
    log(f"  proposed1 θ after the run: {held} bytes in memory kind(s) "
        f"{kinds}; device peak_bytes_in_use so far "
        f"{stats.get('peak_bytes_in_use')}")
    if kinds != [hetmem.HOST] or held != tb:
        raise AssertionError(f"θ is not resident in {hetmem.HOST}: {kinds}")
    converged("proposed1 streamed", streamed, cfg.tol)
    resident1 = methods.run(mesh, cfg, wave, method="proposed1", offload=False,
                            observe=obs)
    converged("proposed1 resident", resident1, cfg.tol)
    agree("proposed1 streamed vs proposed1 resident",
          streamed["velocity_history"], resident1["velocity_history"], TOL_EXACT)
    del resident1
    resident2 = methods.run(mesh, cfg, wave, method="proposed2", offload=False,
                            observe=obs)
    converged("proposed2 resident", resident2, cfg.tol)
    agree("proposed1 streamed vs proposed2 resident",
          streamed["velocity_history"], resident2["velocity_history"], TOL_SOLVERS)


def phase_campaign(jax, out, mesh_n, seed, clock):
    import numpy as np

    from repro.fem import meshgen
    from repro.launch import campaign as campaign_cli
    from repro.surrogate.dataset import shard_paths

    E = meshgen.generate(*mesh_n, pad_elems_to=8).n_elem
    kset = 2
    log(f"  mesh {'x'.join(map(str, mesh_n))}: {E} elements, nspring 150, "
        f"kset {kset}: θ per round = {kset * theta_bytes(E, 150)} bytes")
    argv = ["--method", "proposed2", "--kset", str(kset), "--nspring", "150",
            "--mesh-n", "x".join(map(str, mesh_n)), "--waves", str(kset),
            "--nt", str(CAMPAIGN_STEPS), "--seed", str(seed),
            "--ckpt-dir", os.path.join(out, "ckpt"), "--ckpt-every", "8",
            "--out", os.path.join(out, "shards"), "--shard-size", "1"]
    mark = clock.mark()
    t = time.perf_counter()
    rc = campaign_cli.main(argv)
    wall = time.perf_counter() - t
    cs, cn = clock.since(mark)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  campaign wall {wall!r} s, of which compile {cs!r} s "
        f"({cn} program(s)); device peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')} of bytes_limit "
        f"{stats.get('bytes_limit')}")
    if rc != 0:
        raise AssertionError(f"campaign main returned {rc}")
    shutil.rmtree(os.path.join(out, "ckpt"), ignore_errors=True)  # ~GBs
    paths = shard_paths(os.path.join(out, "shards"))
    ys = [np.load(p)["y"] for p in paths]
    peak = max((float(np.abs(y).max()) for y in ys), default=0.0)
    log(f"  shards: {len(paths)} written, responses {[y.shape for y in ys]}, "
        f"peak |v| {peak!r} m/s at the observed surface node")
    if len(paths) != kset:
        raise AssertionError(f"expected {kset} shards, found {len(paths)}")
    if not (all(np.all(np.isfinite(y)) for y in ys) and peak > 0.0):
        raise AssertionError("shard responses are not finite and non-zero")


def phase_surrogate(out):
    import numpy as np

    from repro.surrogate.model import SurrogateConfig
    from repro.surrogate.train import fit_shards

    _, info = fit_shards(SurrogateConfig(), os.path.join(out, "shards"),
                         steps=8, batch=1)
    losses = [v for (_, tr, va) in info["history"] for v in (tr, va)]
    losses.append(info["val_mae"])
    log(f"  fit_shards: {info['n_shards']} shard(s), history (step, train, "
        f"val) {info['history']}, val MAE {info['val_mae']!r}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError("non-finite surrogate loss")


def phase_sharded(jax, seed):
    from repro.launch.mesh import make_case_mesh

    ec, mesh, waves, obs = small_campaign_inputs((8, 8, 8), 8, seed)
    log(f"  mesh 8x8x8: {mesh.n_elem} elements, nspring 150, "
        f"{len(waves)} cases × {ec.nt} steps, kset 2")
    sharded = run_small_campaign(ec, mesh, waves, obs, "auto",
                                 device_mesh=make_case_mesh(4))
    single = run_small_campaign(ec, mesh, waves, obs, "auto")
    log(f"  sharded: {sharded.rounds_done} round(s) on 4 devices; single "
        f"device: {single.rounds_done} round(s)")
    check_campaign_health("4-device case mesh", sharded)
    check_campaign_health("one device", single)
    if sharded.case_indices.tolist() != single.case_indices.tolist():
        raise AssertionError("case order differs")
    agree("4-device case mesh vs one device", sharded.velocity_history,
          single.velocity_history)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the case-sharded campaign vs one device")
    ap.add_argument("--out", default=os.path.join(ROOT, ".chip_smoke"),
                    help="scratch directory for checkpoints and shards "
                         "(emptied first, removed at the end)")
    args = ap.parse_args(argv)

    from repro.launch.bootstrap import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t_start = time.perf_counter()
    log(f"chip_smoke: --chips {args.chips}, compile cache {cache}")

    results: dict[str, bool] = {}

    def phase(name, fn, *a):
        log(f"[{name}]")
        mark = clock.mark()
        t = time.perf_counter()
        try:
            fn(*a)
            ok = True
        except Exception:  # noqa: BLE001 — report, run the other phases
            traceback.print_exc()
            ok = False
        cs, cn = clock.since(mark)
        log(f"[{name}] {'PASS' if ok else 'FAIL'} in "
            f"{time.perf_counter() - t!r} s (compile {cs!r} s, {cn} "
            f"program(s))")
        results[name] = ok

    phase_device(jax, args.chips)  # SystemExit: no accelerator, no result
    results["a device"] = True
    if args.chips == 4:
        phase("f sharded case mesh", phase_sharded, jax, SEED)
    else:
        out = os.path.abspath(args.out)
        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(out)
        phase("b correctness", phase_correctness, jax, SEED)
        phase("d streamed theta", phase_streamed, jax, STREAM_MESH, SEED)
        phase("c campaign", phase_campaign, jax, out, CAMPAIGN_MESH, SEED,
              clock)
        if results["c campaign"]:
            phase("e surrogate", phase_surrogate, out)
        else:
            log("[e surrogate] FAIL: no shards from (c)")
            results["e surrogate"] = False
        shutil.rmtree(out, ignore_errors=True)
    n_entries = sum(len(f) for _, _, f in os.walk(cache)) if os.path.isdir(cache) else 0
    log(f"total {time.perf_counter() - t_start!r} s, compile "
        f"{clock.seconds!r} s over {clock.count} program(s); compile cache "
        f"{cache}: {n_entries} file(s)")
    failed = [k for k, ok in results.items() if not ok]
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
