"""Ensemble-campaign launcher (paper §3 production run).

Single host::

    PYTHONPATH=src python -m repro.launch.campaign --waves 100 --nt 16000 \
        --kset 2 [--host-devices 2] [--ckpt-dir DIR --ckpt-every 500] \
        [--out shards/] [--method proposed2]

Multi-host (run one copy per node; identical flags except ``--process-id``)::

    PYTHONPATH=src python -m repro.launch.campaign ... \
        --coordinator host0:1234 --num-processes 2 --process-id 0 \
        [--cpu-backend]

Scenario sweeps (``repro.scenario``)::

    PYTHONPATH=src python -m repro.launch.campaign --sweep sweep.json \
        [--autotune [--probe]] [--out shards/] [--ckpt-dir DIR]
    PYTHONPATH=src python -m repro.launch.campaign --scenario ricker-soft-basin

Scheduled (elastic) sweeps — plan groups become leased jobs on disk; workers
join/leave freely and the surrogate can train on shards mid-sweep::

    PYTHONPATH=src python -m repro.launch.campaign --sweep sweep.json \
        --schedule --workers 2 --out shards/ --ckpt-dir DIR \
        [--lease-s 30] [--train-while-generating]
    # or manage workers yourself (same queue, any time, any machine
    # sharing the filesystem):
    PYTHONPATH=src python -m repro.launch.campaign --sweep sweep.json \
        --schedule --worker-id w0 --out shards/ --ckpt-dir DIR

Flags
-----
``--waves / --nt / --mesh-n / --nspring / --seed``
    Ensemble shape: how many band-limited bedrock waves, time steps per
    case, basin mesh cells, springs per quadrature point, wave RNG seed.
``--scenario``
    Run one named catalog scenario (``repro.scenario.CATALOG``) — its wave
    family / soil profile / observation grid, with the ensemble-shape flags
    above still setting ``n_cases``/``nt``/``mesh_n``/``nspring``/``seed``.
``--sweep``
    A sweep spec (JSON file path or inline JSON; see ``docs/scenarios.md``)
    expanded by the planner into compile-signature groups, each run as one
    compiled campaign.  Writes a ``plan.json`` manifest next to the
    checkpoint dir (or into ``--out``), and per-scenario shard dirs under
    ``--out/<scenario>/``.  Single-process only.
``--scenarios``
    A serving feedback log (JSONL written by ``repro.launch.serve
    --feedback-out``): the scenarios the surrogate was least sure about,
    consumed exactly like a sweep — the active-learning loop closes here.
``--schedule / --workers / --lease-s``
    Run the sweep through the elastic work queue
    (``repro.scenario.scheduler``) instead of the serial planner loop:
    compile groups become leased jobs next to ``plan.json``, ``--workers N``
    spawns N worker subprocesses (monitored by the heartbeat watchdog —
    stragglers are flagged before their ``--lease-s`` lease even expires),
    a killed worker's group is requeued by lease takeover and resumed from
    its checkpoint by any survivor.  ``--worker-id NAME`` instead joins the
    queue as a single in-process worker (launch as many as you like,
    whenever you like); ``--max-jobs`` caps how many groups such a worker
    takes before leaving.  The pool parent never opens a JAX backend
    before its workers start, and every worker opens all visible
    accelerators: when a short-lived probe child finds an accelerator, the
    parent refuses ``--workers`` > 1 and ``--train-while-generating``.  A
    sweep whose group failed exits non-zero.
``--train-while-generating [--train-steps N]``
    Overlap surrogate training with generation: the parent streams
    committed scenario shards out of ``--out`` in plan order
    (``ShardStream``) and runs ``fit_stream`` while the workers are still
    producing — deterministic batches regardless of worker count or shard
    arrival, so the result equals a post-hoc ``fit_shards`` on the
    finished dataset.  A training failure fails the launch.
``--autotune / --probe``
    Pick ``(method, npart, kset)`` per plan group with the cost model
    (``--autotune``); ``--probe`` additionally times the shortlisted
    candidates on device.  Without ``--autotune``, ``--method``/``--kset``
    apply to every group.
``--kset``
    Cases advanced per device per round (the generalized 2SET residency).
``--method``
    One of ``repro.fem.methods.METHODS`` (default ``proposed2``).
``--kernel-backend / --ebe-backend / --ms-backend / --tile-e / --tile-p``
    Kernel dispatch (``repro.fem.backend``): ``auto`` (default) runs
    compiled Pallas on TPU/GPU and the jnp oracle elsewhere; ``pallas``
    forces the Pallas kernels (interpret mode off-accelerator — the CI
    smoke's wiring check); ``jnp`` forces the oracle.  The per-kernel
    overrides pin the EBE / multispring kernel independently, and the tile
    flags are the Pallas tiling knobs.  The resolved backend is folded into
    the campaign signature — resuming a checkpoint under a different
    backend is refused.
``--warm-start / --no-warm-start / --precond-every``
    Solver amortization: warm-start each step's CG from the previous δu
    (default on — trajectory equal within solver tolerance, fewer
    iterations), and refresh the EBE block-Jacobi preconditioner every N
    steps instead of every step.  Both are signature-bearing.
``--calibration``
    ``BENCH_kernels.json`` (from ``benchmarks/kernels_bench.py``) feeding
    measured kernel rates into the ``--autotune`` cost model.
``--host-devices`` / ``--devices``
    Force N virtual host devices (local rehearsal) / restrict the case
    mesh to the first N devices (default: every visible device — global
    across processes in a multi-host launch).
``--ckpt-dir / --ckpt-every``
    Checkpoint directory and cadence in time steps.  Kill the launcher
    anywhere and relaunch with the same arguments: it resumes from the
    latest atomic checkpoint bit-identically.  Multi-host runs write
    per-process shards into the same (shared) directory and refuse to
    resume on a different process count.
``--out / --shard-size``
    Write completed responses as ``.npz`` dataset shards for the surrogate
    trainer.  Multi-host launches write each process's owned cases under
    ``OUT/p<NN>/``.
``--trajectories [--obs-every N]``
    Harvest the full observation time series per case (downsampled by the
    ``--obs-every`` stride) instead of the CNN surrogate's full-rate
    target — the training pairs of the parallel-in-time trajectory
    surrogate (``repro.surrogate.trajectory``).  The shard manifest
    records ``{"trajectories": true, "obs_every": N}`` so trainers can
    check the stride.  Plain-campaign path only (not ``--sweep``).
``--coordinator / --num-processes / --process-id``
    ``jax.distributed`` topology: process 0's ``host:port`` coordination
    address, world size, and this process's rank.
``--cpu-backend``
    Force ``JAX_PLATFORMS=cpu`` — the multi-process rehearsal/test mode.
``--stop-after-steps``
    Fault injection: the CI kill-and-resume smoke uses it to exit cleanly
    right after a mid-campaign checkpoint, exactly as a SIGKILL at that
    point would leave the directory.
``--health / --no-health``
    Per-case numerical-health guards (``repro.core.health``, default on):
    every case carries a sticky health word through the Newmark scan; a
    case whose carry, spring state, or solver output goes non-finite is
    *frozen* in place (masked arithmetic — sibling cases in the same vmap
    round are untouched, bit-identically), excluded from shard output, and
    recorded as a quarantine entry in the shard manifest (plain path) or
    the plan manifest (sweeps — where the elastic scheduler additionally
    requeues the group once with a tighter-tolerance fallback config).
    The flag is signature-bearing: guarded and unguarded campaigns never
    share checkpoints.
``--inject``
    Deterministic fault injection (``repro.core.faults``) for chaos
    rehearsal, e.g. ``--inject nan_at_step=5,case=1`` poisons one bedrock
    wave sample so the health machinery above has something to catch.
    Plain campaign path only; the spec is part of the wave data and hence
    the campaign signature.
``--profile-dir DIR``
    Run the campaign under ``jax.profiler.trace(DIR)``: the device's
    operations, named by the program's ``repro.*`` scopes, and the host's
    ``repro.campaign.chunk / fetch / bank / checkpoint`` spans on one clock
    (docs/campaign_runbook.md, "Profiling a campaign").  Plain campaign
    path only.
"""
import argparse
import contextlib
import os
import sys

from repro.launch.bootstrap import force_host_devices, parse_distributed

force_host_devices()
parse_distributed()  # pre-jax-import env effects (--cpu-backend)

import jax  # noqa: E402  (after XLA_FLAGS / JAX_PLATFORMS)
import numpy as np  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=8)
    ap.add_argument("--nt", type=int, default=64)
    ap.add_argument("--mesh-n", default="3x3x3", help="basin mesh cells, e.g. 3x3x3")
    ap.add_argument("--nspring", type=int, default=12)
    ap.add_argument("--kset", type=int, default=2, help="cases per device per round")
    ap.add_argument("--method", default="proposed2")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "jnp", "pallas", "pallas_interpret"],
                    help="kernel dispatch (repro.fem.backend)")
    ap.add_argument("--ebe-backend", default="",
                    help="override the EBE kernel backend only")
    ap.add_argument("--ms-backend", default="",
                    help="override the multispring kernel backend only")
    ap.add_argument("--tile-e", type=int, default=512,
                    help="Pallas EBE kernel elements per tile")
    ap.add_argument("--tile-p", type=int, default=256,
                    help="Pallas multispring kernel points per tile")
    ap.add_argument("--warm-start", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="warm-start each step's CG from the previous δu")
    ap.add_argument("--precond-every", type=int, default=1,
                    help="refresh the EBE preconditioner every N steps")
    ap.add_argument("--calibration", default=None,
                    help="BENCH_kernels.json feeding the --autotune cost model")
    ap.add_argument("--scenario", default=None,
                    help="named catalog scenario (repro.scenario.CATALOG)")
    ap.add_argument("--sweep", default=None,
                    help="scenario sweep spec: JSON file path or inline JSON")
    ap.add_argument("--scenarios", default=None, metavar="FEEDBACK",
                    help="serving feedback log (JSONL of high-uncertainty "
                         "scenarios) consumed as a sweep — the active-"
                         "learning loop back from repro.launch.serve")
    ap.add_argument("--autotune", action="store_true",
                    help="pick (method, npart, kset) per plan group")
    ap.add_argument("--probe", action="store_true",
                    help="with --autotune: on-device microbenchmark probe")
    ap.add_argument("--schedule", action="store_true",
                    help="run the sweep through the elastic work queue")
    ap.add_argument("--workers", type=int, default=0,
                    help="with --schedule: spawn N monitored worker "
                         "subprocesses (0/1 = single worker)")
    ap.add_argument("--lease-s", type=float, default=30.0,
                    help="job lease lifetime; an expired lease is requeued")
    ap.add_argument("--worker-id", default=None,
                    help="with --schedule: join the queue as this single "
                         "worker (user-managed pool)")
    ap.add_argument("--max-jobs", type=int, default=0,
                    help="with --worker-id: leave after completing N groups")
    ap.add_argument("--train-while-generating", action="store_true",
                    help="overlap fit_stream with generation (needs --out)")
    ap.add_argument("--train-steps", type=int, default=120,
                    help="fit_stream optimizer steps for "
                         "--train-while-generating")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="devices on the case axis (default: all visible)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="time steps between mid-round checkpoints")
    ap.add_argument("--out", default=None, help="dataset shard directory")
    ap.add_argument("--shard-size", type=int, default=16)
    ap.add_argument("--trajectories", action="store_true",
                    help="harvest obs-every-strided response histories "
                         "(trajectory-surrogate training pairs) into --out")
    ap.add_argument("--obs-every", type=int, default=1,
                    help="with --trajectories: record every Nth time step")
    ap.add_argument("--stop-after-steps", type=int, default=None,
                    help="fault injection: exit after this many global steps")
    ap.add_argument("--health", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="per-case numerical-health guards (repro.core."
                         "health): freeze diverged cases, exclude them from "
                         "shards, record them in the quarantine manifest")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="deterministic fault injection (repro.core.faults): "
                         "e.g. 'nan_at_step=5,case=1' poisons one bedrock "
                         "wave sample mid-campaign (plain campaign path "
                         "only) — the chaos-smoke rehearsal knob")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="write a profiler trace of the campaign to DIR "
                         "(plain campaign path only)")
    # multi-host topology (parsed pre-jax-import by parse_distributed; kept
    # here so --help documents them and argparse accepts them)
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator, host:port (process 0)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--cpu-backend", action="store_true",
                    help="force the CPU backend (multi-process rehearsal)")
    args = ap.parse_args(argv)
    scenario_mode = bool(args.sweep or args.scenario or args.scenarios)
    if args.schedule and scenario_mode and not args.worker_id:
        # the pool parent plans, spawns and watches; its workers hold the
        # devices, so it must not initialize a JAX backend before they start
        if args.num_processes > 1:
            raise SystemExit("[campaign] --schedule pools are single-process; "
                             "drop the distributed flags")
        _refuse_scenario_only_flags(args, "[campaign]")
        plan = _make_plan(args, "[campaign]")
        return _run_pool(args, "[campaign]", plan)

    from repro.launch.bootstrap import DistributedArgs, distributed_init

    # rebuilt from the parsed args (not module-level _DIST) so programmatic
    # main([...]) calls honor the distributed flags they pass; on the normal
    # CLI path both views come from the same sys.argv
    distributed_init(DistributedArgs(
        coordinator=args.coordinator, num_processes=args.num_processes,
        process_id=args.process_id, cpu_backend=args.cpu_backend,
    ))
    pid, np_ = jax.process_index(), jax.process_count()
    tag = f"[campaign p{pid}]" if np_ > 1 else "[campaign]"

    from repro.launch.mesh import make_case_mesh
    from repro.surrogate.dataset import EnsembleConfig, save_shards

    if np_ > 1 and args.devices and args.devices != len(jax.devices()):
        raise SystemExit(
            f"{tag} --devices {args.devices} with {np_} processes: a "
            f"multi-host campaign must use every device on the global case "
            f"mesh ({len(jax.devices())}); drop --devices"
        )
    n_dev = args.devices or len(jax.devices())
    dmesh = make_case_mesh(n_dev) if n_dev > 1 else None

    if args.trajectories and args.obs_every < 1:
        raise SystemExit(f"{tag} --obs-every must be ≥ 1, got {args.obs_every}")
    if scenario_mode:
        _refuse_scenario_only_flags(args, tag)
        return _run_scenarios(args, tag, np_, dmesh)

    cfg = EnsembleConfig(
        n_waves=args.waves, nt=args.nt,
        mesh_n=tuple(int(x) for x in args.mesh_n.split("x")),
        nspring=args.nspring, seed=args.seed, kset=args.kset,
    )
    B = args.kset * n_dev
    print(f"{tag} {args.waves} waves × {args.nt} steps, method={args.method}, "
          f"{n_dev} device(s) × kset={args.kset} → rounds of {B}"
          + (f" across {np_} processes" if np_ > 1 else ""))

    from repro.campaign import CampaignConfig, run_campaign
    from repro.fem import backend as fem_backend, meshgen
    from repro.surrogate.dataset import random_band_limited_waves, simulation_config

    sim = simulation_config(cfg, **_sim_knobs(args))
    if args.health:
        import dataclasses as _dc

        sim = _dc.replace(sim, health=True)
    kb = fem_backend.resolve(sim)
    print(f"{tag} kernel backend: {kb.describe()} "
          f"warm_start={sim.warm_start} precond_every={sim.precond_every} "
          f"health={sim.health}")
    mesh = meshgen.generate(*cfg.mesh_n, pad_elems_to=8)
    waves = random_band_limited_waves(cfg)
    from repro.core import faults

    inject = faults.parse(args.inject)
    if inject is not None:
        waves = faults.apply_wave_fault(inject, waves)
        print(f"{tag} [inject] {inject.describe()}")
    obs = mesh.surface[len(mesh.surface) // 2 : len(mesh.surface) // 2 + 1]
    with (jax.profiler.trace(args.profile_dir) if args.profile_dir
          else contextlib.nullcontext()):
        res = run_campaign(
            mesh, sim, waves, observe=obs,
            campaign=CampaignConfig(
                kset=args.kset, method=args.method, seed=args.seed,
                checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
            ),
            device_mesh=dmesh,
            stop_after_steps=args.stop_after_steps,
        )
    if args.profile_dir:
        print(f"{tag} profile written under {args.profile_dir}")
    if res.resumed_from is not None:
        print(f"{tag} [resume] from checkpoint step {res.resumed_from}")
    if not res.completed:
        print(f"{tag} [stopped] after {res.steps_done} global steps "
              f"({res.rounds_done} rounds banked) — relaunch to resume")
        return 0
    y = res.velocity_history[:, :, 0, :]
    # a process can own only padded lanes (waves ≤ its round offset) → empty
    stats = (f", peak |v| = {np.abs(y).max():.3e} m/s, "
             f"mean solver iters {res.iters.mean():.1f}" if len(y) else "")
    print(f"{tag} [done] {len(y)} responses"
          + (f" (cases {res.case_indices.min()}–{res.case_indices.max()} of "
             f"{args.waves})" if np_ > 1 and len(y) else "") + stats)
    diverged = np.zeros(0, np.int64)
    keep = np.ones(len(y), bool)
    if res.health.size:
        from repro.core import health as health_mod

        diverged = res.diverged_cases()
        keep = ~np.asarray(health_mod.diverged(res.health))
        print(f"{tag} [health] {len(res.health)} case(s) guarded, "
              f"{diverged.size} diverged, "
              f"{int(res.nonconverged.sum())} non-converged solver step(s)")
        for c in diverged:
            i = int(np.argwhere(res.case_indices == c)[0, 0])
            print(f"{tag} [quarantine] case {int(c)}: "
                  f"{health_mod.describe(res.health[i])} — excluded from "
                  f"shard output")
    if args.out:
        out_dir = args.out if np_ == 1 else f"{args.out}/p{pid:02d}"
        y_out, meta = y, None
        if args.trajectories:
            # the trajectory surrogate's target: the same history, strided —
            # the wave stays full-rate (seqmodel strides it at train time)
            y_out = y[:, ::args.obs_every]
            meta = {"trajectories": True, "obs_every": args.obs_every}
        if diverged.size:  # quarantine record rides the shard manifest
            meta = {**(meta or {}),
                    "quarantine": [int(c) for c in diverged]}
        paths = save_shards(
            out_dir, waves[res.case_indices[keep]].astype(np.float32),
            y_out[keep].astype(np.float32), shard_size=args.shard_size,
            meta=meta,
        )
        kind = (f"trajectory (obs_every={args.obs_every}) "
                if args.trajectories else "")
        print(f"{tag} [shards] wrote {len(paths)} {kind}shard(s) to {out_dir}")
    return 0


def _sim_knobs(args) -> dict:
    """CLI kernel-backend + solver-amortization flags → SeismicConfig fields."""
    return dict(
        backend=args.kernel_backend, ebe_backend=args.ebe_backend,
        ms_backend=args.ms_backend, tile_e=args.tile_e, tile_p=args.tile_p,
        warm_start=args.warm_start, precond_every=args.precond_every,
    )


def _refuse_scenario_only_flags(args, tag) -> None:
    if args.trajectories:
        raise SystemExit(f"{tag} --trajectories rides the plain campaign "
                         f"path; drop --scenario/--sweep/--scenarios")
    if args.profile_dir:
        raise SystemExit(f"{tag} --profile-dir rides the plain campaign path; "
                         f"drop --scenario/--sweep/--scenarios")
    if args.inject:
        raise SystemExit(f"{tag} --inject rides the plain campaign path "
                         f"(scenario sweeps generate their own waves); "
                         f"drop --scenario/--sweep/--scenarios")
    if sum(map(bool, (args.sweep, args.scenario, args.scenarios))) > 1:
        raise SystemExit(
            f"{tag} pass one of --scenario / --sweep / --scenarios")


def _make_plan(args, tag):
    """--scenario / --sweep / --scenarios → the compile-grouped plan (host
    work only: no JAX backend is touched)."""
    import dataclasses

    from repro import scenario as sc

    if args.scenarios:
        from repro.serving.feedback import feedback_plan

        plan = feedback_plan(args.scenarios)
    elif args.sweep:
        plan = sc.make_plan(sc.sweep_from_json(args.sweep))
    else:
        scn = dataclasses.replace(
            sc.get(args.scenario),
            n_cases=args.waves, nt=args.nt, seed=args.seed,
            mesh_n=tuple(int(x) for x in args.mesh_n.split("x")),
            nspring=args.nspring,
        )
        plan = sc.make_plan([scn])
    print(f"{tag} plan: {plan.n_scenarios} scenario(s) in {len(plan.groups)} "
          f"compile group(s), {plan.n_cases} case(s)"
          + (" [autotune]" if args.autotune else f" method={args.method}"))
    return plan


def _run_scenarios(args, tag, np_, dmesh) -> int:
    """--scenario / --sweep: plan + run compile-grouped scenario campaigns."""
    from repro import scenario as sc

    if np_ > 1:
        raise SystemExit(
            f"{tag} --scenario/--sweep are single-process for now (multi-host "
            f"campaigns take the plain flag path); drop the distributed flags"
        )
    plan = _make_plan(args, tag)
    from repro.fem import backend as fem_backend

    kb = fem_backend.resolve(backend=args.kernel_backend,
                             ebe=args.ebe_backend or None,
                             multispring=args.ms_backend or None,
                             tile_e=args.tile_e, tile_p=args.tile_p)
    print(f"{tag} kernel backend: {kb.describe()} "
          f"warm_start={args.warm_start} precond_every={args.precond_every}")
    if args.schedule:
        return _run_worker(args, tag, plan, dmesh)
    run = sc.run_plan(
        plan, autotune=args.autotune, probe=args.probe,
        method=args.method, kset=args.kset, health=args.health,
        calibration=args.calibration, **_sim_knobs(args),
        device_mesh=dmesh, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        out_dir=args.out, shard_size=args.shard_size,
        stop_after_steps=args.stop_after_steps,
        log=lambda m: print(f"{tag} {m}"),
    )
    failed = {k: st["error"] for k, st in run.group_stats.items()
              if st.get("failed")}
    for key, err in failed.items():
        print(f"{tag} [failed] group {key[:8]}: {err}")
    if len(run.scenarios) < plan.n_scenarios:
        print(f"{tag} [stopped] {len(run.scenarios)}/{plan.n_scenarios} "
              f"scenario(s) finished"
              + (f", {len(failed)} group(s) failed" if failed
                 else " — relaunch to resume"))
        return 1 if failed else 0
    for name, sr in run.scenarios.items():
        peak = float(np.abs(sr.responses).max()) if sr.responses.size else 0.0
        print(f"{tag} [done] {name}: {len(sr.waves)} case(s), "
              f"peak |v| = {peak:.3e} m/s"
              + (f", shards → {sr.shard_dir}" if sr.shard_dir else ""))
    if run.manifest_path:
        print(f"{tag} [plan] manifest → {run.manifest_path}")
    return 0


def _group_knobs(args) -> dict:
    """CLI flags → ``run_worker``/``run_plan`` group-execution keywords."""
    return dict(
        autotune=args.autotune, probe=args.probe,
        method=args.method, kset=args.kset, calibration=args.calibration,
        ckpt_every=args.ckpt_every, health=args.health, **_sim_knobs(args),
    )


def _worker_cmd(args, worker: str) -> list:
    """Re-invocation of this CLI as one queue worker child."""
    cmd = [sys.executable, "-m", "repro.launch.campaign",
           "--schedule", "--worker-id", worker,
           "--lease-s", str(args.lease_s),
           "--waves", str(args.waves), "--nt", str(args.nt),
           "--mesh-n", args.mesh_n, "--nspring", str(args.nspring),
           "--seed", str(args.seed), "--kset", str(args.kset),
           "--method", args.method,
           "--kernel-backend", args.kernel_backend,
           "--tile-e", str(args.tile_e), "--tile-p", str(args.tile_p),
           "--precond-every", str(args.precond_every),
           "--shard-size", str(args.shard_size)]
    cmd += ["--warm-start"] if args.warm_start else ["--no-warm-start"]
    cmd += ["--health"] if args.health else ["--no-health"]
    for flag, val in (("--sweep", args.sweep), ("--scenario", args.scenario),
                      ("--scenarios", args.scenarios),
                      ("--ebe-backend", args.ebe_backend),
                      ("--ms-backend", args.ms_backend),
                      ("--calibration", args.calibration),
                      ("--ckpt-dir", args.ckpt_dir), ("--out", args.out)):
        if val:
            cmd += [flag, str(val)]
    if args.ckpt_every:
        cmd += ["--ckpt-every", str(args.ckpt_every)]
    for flag, on in (("--autotune", args.autotune), ("--probe", args.probe),
                     ("--cpu-backend", args.cpu_backend)):
        if on:
            cmd.append(flag)
    if args.host_devices:
        cmd += ["--host-devices", str(args.host_devices)]
    if args.devices:
        cmd += ["--devices", str(args.devices)]
    return cmd


def _queue_config(args, tag):
    from repro.scenario import scheduler as sched

    if not (args.ckpt_dir or args.out):
        raise SystemExit(f"{tag} --schedule needs --ckpt-dir or --out to "
                         f"host the on-disk queue")
    return sched.SchedulerConfig(lease_s=args.lease_s)


def _run_worker(args, tag, plan, dmesh) -> int:
    """--schedule --worker-id: one in-process worker of the elastic queue
    (a worker of a user-managed pool; the single worker of a pool launch
    without ``--worker-id`` is spawned by :func:`_run_pool`)."""
    from repro.scenario import scheduler as sched

    cfg = _queue_config(args, tag)
    s = sched.run_worker(
        plan, worker=args.worker_id, scheduler=cfg, device_mesh=dmesh,
        ckpt_dir=args.ckpt_dir, out_dir=args.out,
        shard_size=args.shard_size, max_jobs=args.max_jobs,
        stop_after_steps=args.stop_after_steps,
        log=lambda m: print(f"{tag} {m}"), **_group_knobs(args),
    )
    print(f"{tag} [worker {s.worker}] done={len(s.done)} "
          f"failed={len(s.failed)} preempted={len(s.preempted)} "
          f"quarantined={len(s.quarantined)} settled={s.settled}"
          + (f" DEAD groups: {s.dead}" if s.dead else ""))
    return 1 if s.dead else 0


_PROBE = "import jax; print(jax.default_backend())"


def _child_platform(args) -> str:
    """The JAX backend a spawned worker would open.  The parent must not
    open one itself (it would then hold the chip its workers need), so a
    short-lived probe child answers and exits before any worker starts."""
    if args.cpu_backend or os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return "cpu"
    import subprocess

    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, timeout=600)
    lines = r.stdout.split()
    if r.returncode or not lines:
        raise SystemExit(f"JAX backend probe failed (rc={r.returncode}): "
                         f"{r.stderr.strip()[-2000:]}")
    return lines[-1]


def _run_pool(args, tag, plan) -> int:
    """--schedule without --worker-id: spawn and watch a worker pool.

    The parent never initializes a JAX backend before its workers run: a
    process that has touched the accelerator holds it, and a worker that
    then needs it fails or hangs.  Every worker opens all visible devices,
    so on an accelerator host only one worker may run, and the parent may
    not train next to it."""
    import subprocess
    import threading
    import time as _time

    from repro.scenario import scheduler as sched

    cfg = _queue_config(args, tag)
    if args.train_while_generating and not args.out:
        raise SystemExit(f"{tag} --train-while-generating streams shards "
                         f"from --out; pass --out")
    n = max(1, args.workers)
    env = None  # workers inherit this environment
    if n > 1 or args.train_while_generating:
        platform = _child_platform(args)
        if platform != "cpu" and n > 1:
            raise SystemExit(
                f"{tag} --workers {n} on a {platform} host: every worker "
                f"opens all visible accelerators, and a second process "
                f"cannot hold a chip the first one holds.  Run --workers 1, "
                f"join workers from other hosts with --worker-id, or run CPU "
                f"workers with --cpu-backend (or JAX_PLATFORMS=cpu)")
        if platform != "cpu":
            raise SystemExit(
                f"{tag} --train-while-generating on a {platform} host: the "
                f"parent would open the chip its worker holds.  Train after "
                f"the sweep (repro.surrogate.train.fit_shards on --out), or "
                f"run with --cpu-backend (or JAX_PLATFORMS=cpu)")
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}  # what the probe found
    names = [f"w{i}" for i in range(n)]
    qdir = sched.queue_dir_for(args.ckpt_dir, args.out)
    os.makedirs(qdir, exist_ok=True)
    print(f"{tag} [schedule] {len(plan.groups)} job(s), {n} worker(s), "
          f"lease {args.lease_s:.0f}s, queue → {qdir}")
    procs, logs = [], []
    for w in names:
        lp = os.path.join(qdir, f"{w}.log")
        lf = open(lp, "w")
        procs.append(subprocess.Popen(
            _worker_cmd(args, w), stdout=lf, stderr=subprocess.STDOUT,
            env=env))
        logs.append((lp, lf))

    trainer: dict = {}

    def train():
        from repro.surrogate.dataset import ShardStream
        from repro.surrogate.model import SurrogateConfig
        from repro.surrogate.train import fit_stream

        order = [s.name for g in plan.groups for s in g.scenarios]
        stream = ShardStream.from_cache(args.out, order,
                                        timeout_s=max(600.0, args.lease_s * 40))
        try:
            trainer["params"], trainer["info"] = fit_stream(
                SurrogateConfig(), stream, steps=args.train_steps)
        except Exception as e:  # noqa: BLE001 — surface, don't kill the sweep
            trainer["error"] = f"{type(e).__name__}: {e}"

    tthread = None
    if args.train_while_generating:
        tthread = threading.Thread(target=train, daemon=True)
        tthread.start()
        print(f"{tag} [schedule] fit_stream training concurrently "
              f"({args.train_steps} steps)")

    watch = sched.QueueWatch(qdir, names)
    while any(p.poll() is None for p in procs):
        _time.sleep(min(2.0, max(0.5, args.lease_s / 3)))
        rep = watch.poll()
        if rep and rep.slow_hosts:
            slow = ", ".join(names[i] for i in rep.slow_hosts)
            print(f"{tag} [watchdog] straggler(s): {slow} (heartbeat "
                  f"{rep.worst_s:.1f}s vs median {rep.median_s:.1f}s)")
    rcs = [p.wait() for p in procs]
    for _, lf in logs:
        lf.close()
    if tthread is not None:
        tthread.join()
        if "error" in trainer:
            print(f"{tag} [train] FAILED: {trainer['error']}")
        else:
            info = trainer["info"]
            print(f"{tag} [train] val MAE {info['val_mae']:.4f} over "
                  f"{info['n_shards']} shard(s), waited "
                  f"{info['stream_wait_s']:.1f}s on generation")

    q = sched.JobQueue(qdir, cfg)
    dead = [g.key for g in plan.groups if q.state(g.key) == "dead"]
    ok = (q.settled(plan) and not dead and not any(rcs)
          and "error" not in trainer)
    for w, rc, (lp, _) in zip(names, rcs, logs):
        if rc:
            print(f"{tag} [schedule] worker {w} exited rc={rc} — see {lp}")
    if dead:
        print(f"{tag} [schedule] DEAD group(s) after retries: {dead}")
    print(f"{tag} [schedule] {'plan settled' if ok else 'plan NOT settled'}; "
          f"manifest → {os.path.join(args.ckpt_dir or args.out, 'plan.json')}")
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.bootstrap import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
