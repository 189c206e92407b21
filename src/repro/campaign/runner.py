"""Sharded ensemble campaigns with checkpoint/resume (the paper's §3 run).

A campaign advances ``M`` independent earthquake cases through the chosen
solution method in *rounds* of ``B = kset × n_devices`` cases, where
``n_devices`` counts every device on the case mesh — across **all
processes** of a multi-host launch:

* the case axis is sharded over a 1-D device mesh (``launch.mesh.
  make_case_mesh``) with ``shard_map`` — cases are embarrassingly parallel,
  so the SPMD program has no collectives at all;
* under ``jax.distributed`` (``launch.bootstrap.distributed_init``) the
  mesh spans every process's devices and :func:`case_topology` assigns each
  process an *owned contiguous slice* of the case axis (process-major, in
  mesh-device order).  Because cases never communicate, each process then
  executes the identical compiled program on its own slice over its local
  devices — node-parallelism exactly as the paper runs its production
  ensemble, with cross-process traffic limited to checkpoint coordination
  barriers (``parallel.distributed``);
* within each device, ``kset`` members run batched (vmap over the
  StreamEngine's ensemble axis — the generalized 2SET of Alg. 4) while the
  per-member spring state streams through the device in ``npart`` blocks
  (Alg. 3);
* time stepping is chunked at ``checkpoint_every`` steps; at every chunk
  boundary the full campaign state — round index, time index, the batched
  Newmark carry with its partitioned spring state, and the accumulated
  observations — goes through :class:`~repro.training.checkpoint.
  CheckpointManager`, so a killed campaign resumes *bit-identically*.
  Multi-host runs checkpoint **only process-local shards** (keyed by
  ``(process_index, step)``); process 0 commits the global manifest after a
  barrier confirms every shard is durable, and completed rounds are banked
  the same way (per-process ``rounds/round_NNNNN.pNN.npz`` shards made
  visible by a process-0 ``.ok`` marker).  A killed N-process campaign
  therefore resumes bit-identically on N processes — and *refuses* to
  resume on any other world size;
* ``M`` need not divide ``B``: the tail round is padded with repeats of the
  last case and the padded lanes are masked out of the result.

The checkpoint cadence maps onto the paper's wall-time budgeting: its
production run holds one 16,000-step case per GPU for hours, so the unit of
loss on preemption must be a chunk of time steps, not a whole case.

Multi-host results stay process-local: each process's
:class:`CampaignResult` holds the cases it owns, with ``case_indices``
mapping them back to rows of the global ``waves`` array (a single-process
run returns ``case_indices == arange(M)``).  Gathering is the caller's
choice — the CLI writes per-process dataset shards; nothing in the runner
ever moves trajectory data between processes.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import zlib
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import health as health_mod
from repro.core.stream import broadcast_kset, pad_kset
from repro.fem import backend as fem_backend, methods
from repro.parallel import distributed as dist
from repro.parallel.sharding import shard_map
from repro.training.checkpoint import CheckpointCorruptError, CheckpointManager


@dataclasses.dataclass(frozen=True)
class CampaignConfig:
    """Campaign shape + fault-tolerance policy (simulation physics lives in
    :class:`~repro.fem.methods.SeismicConfig`).

    ``kset``              ensemble members advanced per device per round.
    ``method``            one of :data:`~repro.fem.methods.METHODS`.
    ``checkpoint_dir``    None disables checkpointing entirely.
    ``checkpoint_every``  time steps between mid-round checkpoints
                          (0 → checkpoint only at round boundaries).
    ``keep``              checkpoints retained (older ones GC'd).
    ``case_axis``         mesh axis name the case dimension shards over.
    ``seed``              recorded in every checkpoint and verified on
                          resume — a checkpoint from a different wave set
                          must not silently splice into this campaign.
    ``scenario_sig``      opaque scenario identity (``repro.scenario``)
                          folded into the campaign signature.  Scenario
                          changes that alter the *mesh* (soil-profile
                          perturbations) are invisible to the wave/config
                          fields below; this string is how they still
                          refuse a foreign checkpoint.
    """

    kset: int = 2
    method: str = "proposed2"
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    keep: int = 3
    case_axis: str = "case"
    seed: int = 0
    scenario_sig: str = ""

    def __post_init__(self):
        if self.kset < 1:
            raise ValueError(f"kset must be ≥ 1, got {self.kset}")
        if self.checkpoint_every < 0:
            raise ValueError("checkpoint_every must be ≥ 0")


class CampaignResult(NamedTuple):
    velocity_history: np.ndarray  # [M_local, nt, n_obs, 3] owned cases only
    iters: np.ndarray             # [M_local, nt] solver iterations per step
    rounds_done: int
    steps_done: int               # global time steps advanced (across rounds)
    completed: bool
    resumed_from: Optional[int]   # checkpoint step number, if resumed
    case_indices: np.ndarray = np.zeros(0, np.int64)
    """Global ``waves`` row of each returned case.  Single-process campaigns
    own everything (``arange(M)``); each process of a multi-host campaign
    gets only its owned slice, in global order."""
    health: np.ndarray = np.zeros(0, np.int32)
    """Per-returned-case health word (:mod:`repro.core.health` bitmask);
    all zeros when every case stayed healthy.  Empty unless the campaign ran
    with ``cfg.health`` guards enabled."""
    nonconverged: np.ndarray = np.zeros(0, np.int64)
    """Per-returned-case count of CG solves that hit ``maxiter`` above
    tolerance.  Empty unless ``cfg.health`` guards were enabled."""

    def diverged_cases(self) -> np.ndarray:
        """Global wave rows of cases that tripped a fatal health bit."""
        if len(self.health) == 0:
            return np.zeros(0, np.int64)
        return self.case_indices[np.asarray(health_mod.diverged(self.health))]


@dataclasses.dataclass(frozen=True)
class CaseTopology:
    """Which slice of every round this process owns, and how to execute it.

    ``n_dev``      devices on the case axis, summed over all processes.
    ``offset``     first case lane (within a round) owned by this process.
    ``local``      cases per round owned here (``kset × local devices``).
    ``exec_mesh``  process-local mesh the chunk program shard_maps over
                   (``None`` → single local device, no shard_map).
    """

    n_dev: int
    process_index: int
    process_count: int
    offset: int
    local: int
    exec_mesh: Any


def case_topology(device_mesh, kset: int) -> CaseTopology:
    """Derive per-process case ownership from a (possibly multi-host) mesh.

    Cross-process XLA programs are unnecessary here (cases are independent)
    and unavailable on the CPU test backend, so a mesh spanning several
    processes is decomposed: each process owns the contiguous block of case
    lanes that sit on its devices — mesh-device order, which
    ``launch.mesh.make_case_mesh`` guarantees is process-major — and
    executes them on a *local* sub-mesh.  Requires every participating
    process to contribute the same number of devices, contiguously; a mesh
    that interleaves processes (or skips one) raises rather than silently
    assigning an empty or scattered slice.
    """
    if device_mesh is None:
        return CaseTopology(1, 0, 1, 0, kset, None)
    devs = list(device_mesh.devices.flat)
    procs = sorted({d.process_index for d in devs})
    if len(procs) == 1:
        exec_mesh = device_mesh if len(devs) > 1 else None
        return CaseTopology(len(devs), 0, 1, 0, kset * len(devs), exec_mesh)
    me = jax.process_index()
    if me not in procs:
        raise ValueError(
            f"case mesh spans processes {procs} but process {me} owns none "
            f"of its devices — every process must participate"
        )
    counts = {p: sum(1 for d in devs if d.process_index == p) for p in procs}
    if len(set(counts.values())) != 1:
        raise ValueError(
            f"case mesh is unbalanced across processes ({counts}); equal "
            f"per-process device counts are required for uniform rounds"
        )
    mine = [i for i, d in enumerate(devs) if d.process_index == me]
    if mine != list(range(mine[0], mine[0] + len(mine))):
        raise ValueError(
            "case mesh interleaves processes; build it with "
            "launch.mesh.make_case_mesh (process-major device order)"
        )
    local_devs = [devs[i] for i in mine]
    exec_mesh = (
        jax.sharding.Mesh(np.asarray(local_devs), device_mesh.axis_names)
        if len(mine) > 1
        else None
    )
    return CaseTopology(
        n_dev=len(devs), process_index=me, process_count=len(procs),
        offset=kset * mine[0], local=kset * len(mine), exec_mesh=exec_mesh,
    )


def _chunk_bounds(nt: int, every: int) -> list[tuple[int, int]]:
    if every <= 0 or every >= nt:
        return [(0, nt)]
    return [(t, min(t + every, nt)) for t in range(0, nt, every)]


def _campaign_sig(campaign: "CampaignConfig", cfg, waves: np.ndarray, B: int, obs,
                  kernel_backend: str = "") -> np.ndarray:
    """Campaign identity, verified on resume.

    Covers everything that shapes the trajectory — the wave *data* itself
    (not just the seed: ``run_campaign`` accepts arbitrary waves), round
    geometry, the *method* and the full simulation physics
    (dt/tol/npart/nspring/…), the solver-amortization knobs
    (``warm_start``/``precond_every`` change the carry structure *and* the
    within-tolerance trajectory), the resolved kernel backend (a checkpoint
    records what produced it — jnp and Pallas agree only to rounding), and
    the observation set — so a checkpoint can never silently splice into a
    run computed under different inputs."""
    M, nt = waves.shape[0], waves.shape[1]
    ident = repr((
        campaign.seed, campaign.kset, campaign.method, campaign.scenario_sig,
        M, nt, B,
        cfg.dt, cfg.tol, cfg.maxiter, cfg.npart, cfg.nspring,
        cfg.inner_iters, cfg.omega0, str(np.dtype(cfg.rdtype)),
        cfg.warm_start, cfg.precond_every, kernel_backend,
        np.asarray(obs).tolist(),
        zlib.crc32(np.ascontiguousarray(waves).tobytes()),
        # appended only when enabled so pre-health checkpoints stay valid
        # for unguarded runs; guards change the carry structure, so guarded
        # and unguarded campaigns must never share a checkpoint
        *(("health",) if cfg.health else ()),
    ))
    # every leaf masked to the positive int32 range: without x64, jax
    # downcasts restored int64 leaves to int32, which must not change the
    # value (the exact seed still participates via the crc over ``ident``)
    return np.asarray(
        [campaign.seed & 0x7FFFFFFF, M, nt, B,
         zlib.crc32(ident.encode()) & 0x7FFFFFFF],
        np.int64,
    )


def _round_path(ckpt_dir: str, r: int, topo: CaseTopology) -> str:
    shard = f".p{topo.process_index:02d}" if topo.process_count > 1 else ""
    return os.path.join(ckpt_dir, "rounds", f"round_{r:05d}{shard}.npz")


def _round_ok_path(ckpt_dir: str, r: int) -> str:
    return os.path.join(ckpt_dir, "rounds", f"round_{r:05d}.ok")


def _bank_round(
    ckpt_dir: str, r: int, vel: np.ndarray, iters: np.ndarray, topo: CaseTopology,
    health: Optional[np.ndarray] = None, nonconverged: Optional[np.ndarray] = None,
) -> None:
    """Persist one completed round atomically — banked rounds are immutable,
    so they are written exactly once instead of being re-serialized into
    every subsequent checkpoint (which would make checkpoint volume grow
    quadratically over a long campaign).

    Multi-host: each process banks only its owned slice
    (``round_NNNNN.pNN.npz``); after a barrier confirms every shard is on
    disk, process 0 commits the round with an ``.ok`` marker — mirroring the
    checkpoint manifest protocol, so a kill between shard writes leaves the
    round uncommitted and it is simply recomputed on resume.
    """
    os.makedirs(os.path.join(ckpt_dir, "rounds"), exist_ok=True)
    path = _round_path(ckpt_dir, r, topo)
    tmp = path + ".tmp"
    extra = {}
    if health is not None:
        extra = {"health": health, "nonconverged": nonconverged}
    with open(tmp, "wb") as f:
        np.savez(f, vel=vel, iters=iters, **extra)
    os.replace(tmp, path)
    if topo.process_count > 1:
        dist.barrier("bank_round")
        if topo.process_index == 0:
            ok = _round_ok_path(ckpt_dir, r)
            with open(ok + ".tmp", "w") as f:
                f.write(f"{topo.process_count}\n")
            os.replace(ok + ".tmp", ok)


def _load_banked_round(
    ckpt_dir: str, r: int, r0: int, topo: CaseTopology
) -> tuple[np.ndarray, np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
    path = _round_path(ckpt_dir, r, topo)
    if topo.process_count > 1 and not os.path.exists(_round_ok_path(ckpt_dir, r)):
        raise ValueError(
            f"checkpoint says round {r0} but banked round {r} was never "
            f"committed (missing {_round_ok_path(ckpt_dir, r)}) — checkpoint "
            f"directory corrupt"
        )
    if not os.path.exists(path):
        raise ValueError(
            f"checkpoint says round {r0} but banked round file {path} is "
            f"missing — checkpoint directory corrupt"
        )
    with np.load(path) as z:
        return (
            z["vel"], z["iters"],
            z["health"] if "health" in z.files else None,
            z["nonconverged"] if "nonconverged" in z.files else None,
        )


def make_campaign_chunk(
    ops: methods.FemOperators,
    method: str,
    obs_idx,
    *,
    device_mesh=None,
    case_axis: str = "case",
):
    """``(chunk_fn, carry0)``: the jitted campaign kernel + one-member carry.

    ``chunk_fn(carry, wave_chunk)`` advances a ``[B, ...]``-batched carry
    through ``wave_chunk [B, ct, 3]`` and returns
    ``(carry', (vel [B, ct, n_obs, 3], iters [B, ct]))``.  With a device
    mesh, the leading case axis is sharded via ``shard_map``; each device
    runs the identical program on its ``kset`` local members.

    With ``ops.cfg.health`` the per-case step is wrapped by
    :func:`repro.core.health.guard_step`: the carry becomes
    ``(inner_carry, health_word, nonconverged)`` — all three checkpoint
    together — and a case whose step goes non-finite is frozen by masked
    arithmetic, so NaN cannot march forward in time (lanes of the vmap are
    already independent of each other).
    """
    step, carry0 = methods.make_ensemble_step(ops, method)
    guarded = bool(ops.cfg.health)
    if guarded:
        step = health_mod.guard_step(step)
        carry0 = health_mod.initial_guard_carry(carry0)
    obs_idx = jnp.asarray(obs_idx)

    def chunk(carry, wave_chunk):
        def body(c, f_t):  # f_t: [B_local, 3]
            c, aux = jax.vmap(step)(c, f_t)
            nm = c[0][0] if guarded else c[0]
            return c, (nm.v[:, obs_idx], aux.iters)

        carry, (vel, iters) = jax.lax.scan(
            body, carry, jnp.swapaxes(wave_chunk, 0, 1)
        )
        return carry, (jnp.swapaxes(vel, 0, 1), jnp.swapaxes(iters, 0, 1))

    if device_mesh is not None and device_mesh.devices.size > 1:
        spec = P(case_axis)
        chunk = shard_map(
            chunk, device_mesh, in_specs=(spec, spec), out_specs=spec
        )
    return jax.jit(chunk), carry0


def run_campaign(
    mesh,
    cfg: methods.SeismicConfig,
    waves,  # [M, nt, 3] bedrock input velocities
    *,
    observe: np.ndarray | None = None,
    campaign: CampaignConfig = CampaignConfig(),
    device_mesh=None,
    stop_after_steps: Optional[int] = None,
) -> CampaignResult:
    """Run (or resume) an ensemble campaign over ``waves``.

    ``device_mesh`` is a 1-D mesh whose ``campaign.case_axis`` shards the
    case dimension (``launch.mesh.make_case_mesh()``); None runs single-
    device.  A mesh spanning several ``jax.distributed`` processes makes
    this a multi-host campaign: every process calls ``run_campaign`` with
    identical arguments, owns the case slice :func:`case_topology` assigns
    it, and returns only its local cases (see ``CampaignResult.
    case_indices``).  ``stop_after_steps`` aborts the campaign at the first
    chunk boundary at or past that many global time steps *after* writing
    its checkpoint — the fault-injection hook the kill-and-resume tests and
    the CI smoke use (a real SIGKILL anywhere is no worse: the previous
    checkpoint is atomic on disk).

    Under ``jax.profiler.trace`` the host's part of the loop shows as
    spans on the device's clock: ``repro.campaign.chunk`` (dispatch of a
    chunk), ``repro.campaign.fetch`` (its ``device_get``),
    ``repro.campaign.bank`` and ``repro.campaign.checkpoint`` (writes).
    """
    waves = np.asarray(waves)
    M, nt = waves.shape[0], waves.shape[1]
    topo = case_topology(device_mesh, campaign.kset)
    if (
        topo.process_count == 1
        and campaign.checkpoint_dir
        and dist.is_distributed()
    ):
        # N uncoordinated processes checkpointing single-process layouts
        # into one (shared) directory would race each other's atomic
        # renames and splice trajectories — refuse rather than corrupt
        raise ValueError(
            f"running under jax.distributed with {dist.process_count()} "
            f"processes but the case mesh spans only this one; pass a "
            f"spanning mesh (launch.mesh.make_case_mesh()) or give each "
            f"process its own checkpoint_dir"
        )
    B = campaign.kset * topo.n_dev        # global round size
    padded, valid = pad_kset(waves, B)
    n_rounds = padded.shape[0] // B
    obs = np.asarray(observe if observe is not None else mesh.surface[:1])
    n_obs = len(obs)

    ops = fem_backend.make_operators(mesh, cfg)
    chunk_fn, carry0 = make_campaign_chunk(
        ops, campaign.method, obs, device_mesh=topo.exec_mesh,
        case_axis=campaign.case_axis,
    )
    carry0_b = broadcast_kset(carry0, topo.local)
    bounds = _chunk_bounds(nt, campaign.checkpoint_every)
    wave_all = jnp.asarray(padded, cfg.rdtype)
    vdt = np.dtype(cfg.rdtype)
    sig = _campaign_sig(
        campaign, cfg, waves, B, obs, ops.kernel_backend.describe()
    )

    mgr = (
        CheckpointManager(
            campaign.checkpoint_dir, keep=campaign.keep,
            process_index=topo.process_index, process_count=topo.process_count,
        )
        if campaign.checkpoint_dir
        else None
    )

    # ---- resume ------------------------------------------------------------
    # Mutable campaign state splits in two: completed rounds are *immutable*
    # and banked once as rounds/round_NNNNN.npz; the checkpoint carries only
    # what still changes (the in-flight carry + this round's partial
    # observations), so checkpoint volume stays O(round), not O(campaign).
    r0, t0 = 0, 0
    carry = carry0_b
    guarded = bool(cfg.health)
    # [(vel, iters, health|None, nonconverged|None)] per completed round
    done_rounds: list[tuple] = []
    cur_vel: list[np.ndarray] = []
    cur_iters: list[np.ndarray] = []
    resumed_from = None
    if mgr is not None:
        meta_like = {"meta": {"sig": sig, "round": np.zeros((), np.int64),
                              "t": np.zeros((), np.int64)}}
        bad_steps: set[int] = set()
        while True:
            restored = mgr.restore_latest(meta_like, skip=bad_steps)
            if restored is None:
                break
            ckpt_step, head = restored
            # verify the signature BEFORE restoring the carry: a mismatched
            # campaign must produce this error, not a pytree-structure one
            if not np.array_equal(np.asarray(head["meta"]["sig"]), sig):
                raise ValueError(
                    f"checkpoint in {campaign.checkpoint_dir} belongs to a "
                    f"different campaign (sig {np.asarray(head['meta']['sig'])} "
                    f"vs {sig}) — refusing to splice trajectories"
                )
            try:
                st = mgr.restore(ckpt_step, {
                    "carry": carry0_b,
                    "vel": np.zeros(()),     # structure-only (shape varies)
                    "iters": np.zeros(()),
                })
            except CheckpointCorruptError as e:
                # the meta head verified but a carry/obs leaf is corrupt —
                # same degradation as restore_latest: lose one chunk, not
                # the campaign
                print(
                    f"[checkpoint] step {ckpt_step} failed checksum "
                    f"verification ({e}) — falling back to the previous "
                    f"committed step",
                    file=sys.stderr,
                )
                bad_steps.add(ckpt_step)
                continue
            r0, t0 = int(head["meta"]["round"]), int(head["meta"]["t"])
            carry = st["carry"]
            for rr in range(r0):
                done_rounds.append(
                    _load_banked_round(campaign.checkpoint_dir, rr, r0, topo)
                )
            if t0 > 0:
                cur_vel = [np.asarray(st["vel"])]
                cur_iters = [np.asarray(st["iters"])]
            resumed_from = ckpt_step
            break

    def _save(r_next: int, t_next: int, carry_next, blocking: bool = False):
        if mgr is None:
            return
        state = {
            "carry": carry_next,
            "vel": (np.concatenate(cur_vel, axis=1) if cur_vel
                    else np.zeros((topo.local, 0, n_obs, 3), vdt)),
            "iters": (np.concatenate(cur_iters, axis=1) if cur_iters
                      else np.zeros((topo.local, 0), np.int64)),
            "meta": {"sig": sig, "round": np.int64(r_next), "t": np.int64(t_next)},
        }
        # the JSON meta is the cross-shard agreement key restore_latest
        # validates: all processes must have banked the same (round, t)
        mgr.save(
            r_next * nt + t_next, state, blocking=blocking,
            meta={"round": int(r_next), "t": int(t_next)},
        )

    # ---- rounds ------------------------------------------------------------
    steps_done = r0 * nt + t0
    completed = r0 >= n_rounds
    stopped = False
    for r in range(r0, n_rounds):
        if r > r0:
            carry, cur_vel, cur_iters, t0 = carry0_b, [], [], 0
        lo = r * B + topo.offset
        wave_r = wave_all[lo : lo + topo.local]
        for a, b in bounds:
            if b <= t0:
                continue  # already restored past this chunk
            a = max(a, t0)
            with jax.profiler.TraceAnnotation("repro.campaign.chunk"):
                carry, (vel, iters) = chunk_fn(carry, wave_r[:, a:b])
            with jax.profiler.TraceAnnotation("repro.campaign.fetch"):
                cur_vel.append(np.asarray(jax.device_get(vel)))
                cur_iters.append(np.asarray(jax.device_get(iters)))
            steps_done = r * nt + b
            if b == nt:  # round complete → bank it once, reset for the next
                round_vel = np.concatenate(cur_vel, axis=1)
                round_iters = np.concatenate(cur_iters, axis=1)
                if guarded:  # final guarded carry = (inner, word, ncg)
                    with jax.profiler.TraceAnnotation("repro.campaign.fetch"):
                        round_health = np.asarray(jax.device_get(carry[1]), np.int32)
                        round_ncg = np.asarray(jax.device_get(carry[2]), np.int64)
                else:
                    round_health = round_ncg = None
                done_rounds.append(
                    (round_vel, round_iters, round_health, round_ncg)
                )
                if mgr is not None:
                    with jax.profiler.TraceAnnotation("repro.campaign.bank"):
                        _bank_round(
                            campaign.checkpoint_dir, r, round_vel, round_iters,
                            topo, round_health, round_ncg,
                        )
                cur_vel, cur_iters = [], []
                completed = r + 1 == n_rounds
                with jax.profiler.TraceAnnotation("repro.campaign.checkpoint"):
                    _save(r + 1, 0, carry0_b, blocking=completed)
            else:
                with jax.profiler.TraceAnnotation("repro.campaign.checkpoint"):
                    _save(r, b, carry)
            if (
                stop_after_steps is not None
                and steps_done >= stop_after_steps
                and not completed
            ):
                stopped = True
                break
        if stopped or completed:
            break
    if mgr is not None:
        with jax.profiler.TraceAnnotation("repro.campaign.checkpoint"):
            mgr.wait()

    nr_done = len(done_rounds)
    # global waves row of each locally-held case, before masking out padding
    ids = (
        np.concatenate(
            [r * B + topo.offset + np.arange(topo.local) for r in range(nr_done)]
        )
        if nr_done
        else np.zeros(0, np.int64)
    )
    vmask = valid[ids]
    done_vel = (
        np.stack([v for v, _, _, _ in done_rounds])
        if nr_done
        else np.zeros((0, topo.local, nt, n_obs, 3), vdt)
    )
    done_iters = (
        np.stack([it for _, it, _, _ in done_rounds])
        if nr_done
        else np.zeros((0, topo.local, nt), np.int64)
    )
    if guarded:
        # a pre-health banked round (health=None) cannot appear here: the
        # health knob is folded into the campaign signature, so resuming a
        # guarded campaign over unguarded rounds refuses before this point
        done_health = (
            np.stack([h for _, _, h, _ in done_rounds])
            if nr_done else np.zeros((0, topo.local), np.int32)
        )
        done_ncg = (
            np.stack([c for _, _, _, c in done_rounds])
            if nr_done else np.zeros((0, topo.local), np.int64)
        )
        health_flat = done_health.reshape(nr_done * topo.local)[vmask]
        ncg_flat = done_ncg.reshape(nr_done * topo.local)[vmask]
    else:
        health_flat = np.zeros(0, np.int32)
        ncg_flat = np.zeros(0, np.int64)
    return CampaignResult(
        velocity_history=done_vel.reshape(nr_done * topo.local, nt, n_obs, 3)[vmask],
        iters=done_iters.reshape(nr_done * topo.local, nt)[vmask],
        rounds_done=nr_done,
        steps_done=steps_done,
        completed=completed,
        resumed_from=resumed_from,
        case_indices=ids[vmask],
        health=health_flat,
        nonconverged=ncg_flat,
    )
