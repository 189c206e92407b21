"""Campaign benchmark: sharded k-set rounds vs the per-case Python loop.

Times the same ensemble (M waves × nt steps on the synthetic basin) two
ways and emits ``BENCH_campaign.json``:

* **baseline** — the pre-campaign path: a Python loop calling
  ``methods.run`` once per case (one trace + one scan per case, single
  device);
* **campaign** — ``repro.campaign.run_campaign``: case axis sharded over
  the host devices, ``kset`` members vmapped per device, one compiled
  chunk program reused across every round.

Throughput is cases/s over the whole ensemble.  The bench runs on the CPU
backend only (it refuses any other): the devices are virtual
(``--xla_force_host_platform_device_count``), so the win comes from
batching + single-compilation amortization rather than real parallel
silicon, and none of its numbers is an accelerator measurement.

``--processes N`` adds a **multi-host scaling** section: the bench respawns
itself as N ``jax.distributed`` CPU processes (1 forced host device each)
sharing one coordination service, each owning its slice of the case axis
exactly as a cluster campaign would, and reports whole-ensemble throughput
— the zero→cluster rehearsal of the paper's node-parallel production run.

Usage:
    PYTHONPATH=src python benchmarks/campaign_bench.py [--smoke] [--out PATH] \
        [--devices 2] [--waves 8] [--nt 16] [--processes 2]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.bootstrap import force_host_devices  # noqa: E402

force_host_devices(flag="--devices", default=2)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.campaign import CampaignConfig, make_campaign_chunk, run_campaign  # noqa: E402
from repro.core.stream import broadcast_kset, pad_kset  # noqa: E402
from repro.fem import backend as fem_backend, meshgen, methods  # noqa: E402
from repro.launch.mesh import make_case_mesh  # noqa: E402
from repro.surrogate.dataset import EnsembleConfig, random_band_limited_waves  # noqa: E402


def _dist_child(args) -> None:
    """One process of the ``--processes N`` scaling run (re-spawned self)."""
    from repro.campaign.runner import case_topology
    from repro.parallel import distributed as dist
    from repro.launch.bootstrap import distributed_init

    distributed_init(coordinator=args.coordinator, num_processes=args.processes,
                     process_id=args.process_id)
    mesh = meshgen.generate(*(int(x) for x in args.mesh_n.split("x")), pad_elems_to=8)
    cfg = methods.SeismicConfig(dt=0.01, tol=1e-6, maxiter=400, npart=2, nspring=12,
                                backend=args.kernel_backend)
    waves = random_band_limited_waves(EnsembleConfig(n_waves=args.waves, nt=args.nt, dt=cfg.dt))
    obs = mesh.surface[:1]
    dmesh = make_case_mesh()  # spans every process
    topo = case_topology(dmesh, args.kset)
    B = args.kset * topo.n_dev

    ops = fem_backend.make_operators(mesh, cfg)
    chunk_fn, carry0 = make_campaign_chunk(ops, args.method, obs,
                                           device_mesh=topo.exec_mesh)
    carry0_b = broadcast_kset(carry0, topo.local)
    padded, _ = pad_kset(waves, B)
    wave_all = jnp.asarray(padded, cfg.rdtype)
    n_rounds = padded.shape[0] // B

    def ensemble_pass():
        out = []
        for r in range(n_rounds):
            lo = r * B + topo.offset
            _, (vel, _) = chunk_fn(carry0_b, wave_all[lo : lo + topo.local])
            out.append(vel)
        return jax.block_until_ready(out)

    dist.barrier("bench_cold")
    t0 = time.perf_counter()
    ensemble_pass()  # includes the one compilation
    dist.barrier("bench_cold_done")
    cold_s = time.perf_counter() - t0
    dist.barrier("bench_steady")
    t0 = time.perf_counter()
    ensemble_pass()
    dist.barrier("bench_steady_done")  # slowest process bounds the ensemble
    steady_s = time.perf_counter() - t0
    if args.process_id == 0:
        with open(args.dist_out, "w") as f:
            json.dump({
                "processes": args.processes,
                "devices_per_process": len(jax.local_devices()),
                "round_size": B, "rounds": n_rounds,
                "total_s_cold": cold_s, "total_s": steady_s,
                "cases_per_s": args.waves / steady_s,
            }, f)


def _run_distributed(args) -> dict:
    """Spawn ``--processes N`` coordinated copies of this bench; returns the
    scaling record process 0 measured (barrier-bracketed, so it reflects the
    slowest process — the ensemble's true completion time)."""
    from repro.parallel.distributed import free_port

    port = free_port()
    work = tempfile.mkdtemp()
    out_path = os.path.join(work, "dist.json")
    procs, logs = [], []
    for pid in range(args.processes):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        cmd = [
            sys.executable, os.path.abspath(__file__), "--dist-child",
            "--coordinator", f"127.0.0.1:{port}",
            "--processes", str(args.processes), "--process-id", str(pid),
            "--dist-out", out_path, "--devices", "1",
            "--waves", str(args.waves), "--nt", str(args.nt),
            "--mesh-n", args.mesh_n, "--kset", str(args.kset),
            "--method", args.method, "--kernel-backend", args.kernel_backend,
        ]
        # log files, not PIPEs: a chatty undrained sibling blocked on a full
        # pipe buffer would stall the whole coordinated fleet at a barrier
        log = open(os.path.join(work, f"p{pid}.log"), "w+")
        logs.append(log)
        procs.append(subprocess.Popen(cmd, env=env, stdout=log,
                                      stderr=subprocess.STDOUT, text=True))
    try:
        for pid, p in enumerate(procs):
            p.wait(timeout=1200)
            if p.returncode != 0:
                logs[pid].seek(0)
                raise RuntimeError(
                    f"distributed bench process {pid} failed:\n"
                    f"{logs[pid].read()[-2000:]}"
                )
    finally:  # one dead process leaves siblings blocked at a barrier
        for p in procs:
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()
    with open(out_path) as f:
        return json.load(f)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny sizes (CI)")
    ap.add_argument("--out", default=os.path.join(os.path.dirname(__file__), "..", "BENCH_campaign.json"))
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--waves", type=int, default=8)
    ap.add_argument("--nt", type=int, default=16)
    ap.add_argument("--mesh-n", default="2x2x2")
    ap.add_argument("--kset", type=int, default=2)
    ap.add_argument("--method", default="proposed2")
    ap.add_argument("--kernel-backend", default="auto",
                    help="repro.fem.backend spec: auto | jnp | pallas | pallas_interpret")
    ap.add_argument("--precond-every", type=int, default=4,
                    help="preconditioner lag measured in the warm_start section")
    ap.add_argument("--processes", type=int, default=1,
                    help="also measure an N-process jax.distributed campaign")
    ap.add_argument("--dist-child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--process-id", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--dist-out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.smoke:
        args.waves, args.nt = 4, 6
    if args.dist_child:
        return _dist_child(args)
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"campaign_bench times the CPU rehearsal (virtual host devices, "
            f"CPU worker processes); on this {jax.default_backend()} host its "
            f"numbers would not be the accelerator's.  Run it with "
            f"JAX_PLATFORMS=cpu; measure the accelerator with chip_smoke.py")

    n_dev = min(args.devices, len(jax.devices()))
    mesh = meshgen.generate(*(int(x) for x in args.mesh_n.split("x")), pad_elems_to=8)
    cfg = methods.SeismicConfig(dt=0.01, tol=1e-6, maxiter=400, npart=2, nspring=12,
                                backend=args.kernel_backend)
    ecfg = EnsembleConfig(n_waves=args.waves, nt=args.nt, dt=cfg.dt)
    waves = random_band_limited_waves(ecfg)
    obs = mesh.surface[:1]

    # --- baseline: per-case Python loop (the pre-campaign dataset path) ----
    t0 = time.perf_counter()
    base_out = [
        np.asarray(methods.run(mesh, cfg, w, method=args.method, observe=obs)["velocity_history"])
        for w in waves
    ]
    base_s = time.perf_counter() - t0
    base_vel = np.stack(base_out)

    # --- campaign: sharded k-set rounds ------------------------------------
    dmesh = make_case_mesh(n_dev) if n_dev > 1 else None
    cc = CampaignConfig(kset=args.kset, method=args.method)

    t0 = time.perf_counter()
    res = run_campaign(mesh, cfg, waves, observe=obs, campaign=cc, device_mesh=dmesh)
    camp_cold_s = time.perf_counter() - t0  # includes the one compilation

    # Steady state: one compiled chunk program reused across every round —
    # what a long campaign sees after its single compile.  Driving the chunk
    # directly (rather than re-calling run_campaign, which builds a fresh
    # jit closure and would re-trace) isolates the per-round compute.
    B = args.kset * n_dev
    ops = fem_backend.make_operators(mesh, cfg)
    chunk_fn, carry0 = make_campaign_chunk(ops, args.method, obs, device_mesh=dmesh)
    carry0_b = broadcast_kset(carry0, B)
    padded, _ = pad_kset(waves, B)
    wave_all = jnp.asarray(padded, cfg.rdtype)
    n_rounds = padded.shape[0] // B

    def steady_pass():
        out = []
        for r in range(n_rounds):
            _, (vel, _) = chunk_fn(carry0_b, wave_all[r * B : (r + 1) * B])
            out.append(vel)
        return jax.block_until_ready(out)

    steady_pass()  # warmup / compile
    t0 = time.perf_counter()
    steady_pass()
    camp_s = time.perf_counter() - t0

    # --- solver amortization: warm start + lagged preconditioner -----------
    # Same waves, same backend, same compiled-chunk shape — only the solver
    # start vector (and preconditioner cadence) changes.  The claim measured:
    # strictly fewer cumulative CG iterations at a tolerance-equal trajectory.
    # health=True: the guarded carry also counts non-converged CG steps per
    # case — the warm-start claim is tolerance-EQUAL trajectories, so these
    # cumulative counts belong in the record (0 means no step was silently
    # served past tolerance; nonzero flags an iteration budget too tight).
    cfg_warm = dataclasses.replace(cfg, warm_start=True, health=True)
    cfg_lag = dataclasses.replace(cfg, warm_start=True, health=True,
                                  precond_every=args.precond_every)
    t0 = time.perf_counter()
    res_warm = run_campaign(mesh, cfg_warm, waves, observe=obs,
                            campaign=cc, device_mesh=dmesh)
    warm_s = time.perf_counter() - t0
    res_lag = run_campaign(mesh, cfg_lag, waves, observe=obs,
                           campaign=cc, device_mesh=dmesh)
    scale = float(np.abs(base_vel).max()) + 1e-30
    iters_cold = int(res.iters.sum())
    warm_section = {
        "iters_total_cold": iters_cold,
        "iters_total_warm": int(res_warm.iters.sum()),
        "iters_total_warm_lagged": int(res_lag.iters.sum()),
        "iters_reduction_warm": 1.0 - res_warm.iters.sum() / max(1, iters_cold),
        "nonconverged_steps_warm": int(res_warm.nonconverged.sum()),
        "nonconverged_steps_warm_lagged": int(res_lag.nonconverged.sum()),
        "diverged_cases_warm": [int(c) for c in res_warm.diverged_cases()],
        "precond_every": args.precond_every,
        "total_s_cold_start": camp_cold_s,
        "total_s_warm_start": warm_s,
        "max_rel_disagreement_warm": float(
            np.abs(res_warm.velocity_history - res.velocity_history).max()) / scale,
        "max_rel_disagreement_warm_lagged": float(
            np.abs(res_lag.velocity_history - res.velocity_history).max()) / scale,
    }

    agree = float(np.abs(res.velocity_history - base_vel).max()) / scale
    payload = {
        "bench": "campaign",
        "backend": jax.default_backend(),
        "kernel_backend": args.kernel_backend,
        "devices": n_dev,
        "waves": args.waves,
        "nt": args.nt,
        "kset": args.kset,
        "method": args.method,
        "round_size": args.kset * n_dev,
        "smoke": args.smoke,
        "baseline_per_case_loop": {
            "total_s": base_s,
            "cases_per_s": args.waves / base_s,
        },
        "campaign_sharded_kset": {
            "total_s": camp_s,
            "total_s_cold": camp_cold_s,
            "cases_per_s": args.waves / camp_s,
            "rounds": res.rounds_done,
        },
        "speedup": base_s / camp_s,
        "max_rel_disagreement_vs_baseline": agree,
        "warm_start": warm_section,
    }
    if args.processes > 1:
        payload["distributed_scaling"] = _run_distributed(args)
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload, indent=2))
    print(f"wrote {out_path}")
    return payload


if __name__ == "__main__":
    main()
