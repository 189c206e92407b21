"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --shape train_4k \
        --steps 100 --offload-optimizer [--multi-pod] [--host-devices N]

On a real TPU pod this runs under ``jax.distributed.initialize()`` (one
process per host, same command everywhere).  ``--host-devices`` forces N
virtual host devices for local rehearsal of the distributed path.  The
launcher wires: config → sharded init → (offloaded) optimizer → prefetched
data → watchdog → async checkpoints, and resumes from the latest checkpoint
if one exists (fault tolerance: kill it mid-run and relaunch).
"""
import argparse

from repro.launch.bootstrap import force_host_devices

force_host_devices()

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 → data×model")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--offload-optimizer", action="store_true")
    ap.add_argument("--npart", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--distributed", action="store_true",
                    help="multi-host: call jax.distributed.initialize()")
    args = ap.parse_args()

    if args.distributed:
        jax.distributed.initialize()

    from repro.configs import ARCHS, SHAPES
    from repro.core.offload import OffloadConfig
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as T
    from repro.parallel import sharding as sh
    from repro.training import data as data_mod
    from repro.training.checkpoint import CheckpointManager
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_step import TrainConfig, init_train_state, make_train_step

    cfg = ARCHS[args.arch]
    shape = SHAPES[args.shape]
    if args.reduced:
        cfg = cfg.reduced()
        global_batch, seq = 8, 128
    else:
        global_batch, seq = shape.global_batch, shape.seq_len

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        from repro.launch.mesh import make_auto_mesh

        mesh = make_auto_mesh(dims, axes)
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    rules = sh.rules_for(cfg, mesh, kind="train", global_batch=global_batch, seq_len=seq)

    tcfg = TrainConfig(
        adamw=AdamWConfig(learning_rate=1e-3, warmup_steps=50),
        offload=OffloadConfig(optimizer_state=args.offload_optimizer, optimizer_npart=args.npart),
    )

    with mesh, sh.use_mesh(mesh, rules):
        params, pspecs = T.init_params(cfg, jax.random.key(0))
        pshard = sh.tree_shardings(pspecs, mesh, rules)
        params = jax.tree_util.tree_map(lambda p, s: jax.device_put(p, s), params, pshard)
        opt = init_train_state(cfg, tcfg, params)
        step = jax.jit(make_train_step(cfg, tcfg))

        mgr = CheckpointManager(args.ckpt_dir)
        start = 0
        restored = mgr.restore_latest({"params": params}, shardings={"params": pshard})
        if restored is not None:
            start, state = restored
            params = state["params"]
            print(f"[resume] from checkpoint step {start}")

        dcfg = data_mod.DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                   global_batch=global_batch,
                                   frontend=cfg.frontend, d_model=cfg.d_model,
                                   n_frontend_tokens=cfg.n_frontend_tokens)
        it = data_mod.Prefetcher(data_mod.batches(dcfg), depth=2)
        for i in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            params, opt, metrics = step(params, opt, batch)
            if i % 10 == 0:
                print(f"step {i:5d}  nll {float(metrics['nll']):.4f}")
            if args.ckpt_every and i and i % args.ckpt_every == 0:
                mgr.save(i, {"params": params})
        mgr.save(args.steps, {"params": params}, blocking=True)
        it.close()
        print("training complete")


if __name__ == "__main__":
    from repro.launch.bootstrap import enable_compile_cache

    enable_compile_cache()
    main()
