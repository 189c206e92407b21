"""End-to-end LM training driver with the paper's optimizer-state offload.

    PYTHONPATH=src python examples/train_lm.py --steps 50            # tiny, fast
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
    PYTHONPATH=src python examples/train_lm.py --arch qwen3-1.7b --reduced

Trains a decoder LM on the synthetic bigram stream with checkpoint/restart
and the heterogeneous-memory optimizer (Adam moments host-resident,
streamed through the device in blocks — Algorithm 3 applied to training).
On the CPU container the placements are annotations; on TPU they are real.
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def preset_100m():
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32768,
        tie_embeddings=True, dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="registry arch id (reduced config)")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--offload", action="store_true", default=True)
    ap.add_argument("--no-offload", dest="offload", action="store_false")
    ap.add_argument("--npart", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    from repro.configs import ARCHS
    from repro.core.offload import OffloadConfig
    from repro.models import transformer as T
    from repro.training import data as data_mod
    from repro.training.checkpoint import CheckpointManager
    from repro.training.elastic import StepWatchdog
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_step import TrainConfig, init_train_state, make_train_step

    if args.arch:
        cfg = ARCHS[args.arch].reduced()
    elif args.preset == "100m":
        cfg = preset_100m()
    else:
        cfg = ARCHS["qwen3-1.7b"].reduced()

    tcfg = TrainConfig(
        adamw=AdamWConfig(learning_rate=3e-3, warmup_steps=20, weight_decay=0.01),
        offload=OffloadConfig(optimizer_state=args.offload, optimizer_npart=args.npart),
    )
    params, _ = T.init_params(cfg, jax.random.key(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    print(f"model: {cfg.name} — {n_params/1e6:.1f}M params, offload={args.offload} "
          f"(moments {'host-resident, streamed in ' + str(args.npart) + ' blocks' if args.offload else 'device-resident'})")

    opt = init_train_state(cfg, tcfg, params)
    step = jax.jit(make_train_step(cfg, tcfg))
    dcfg = data_mod.DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                               global_batch=args.batch)
    it = data_mod.Prefetcher(data_mod.batches(dcfg), depth=2)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    wd = StepWatchdog(n_hosts=1)

    t0 = time.time()
    for i in range(args.steps):
        t_step = time.time()
        batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, metrics = step(params, opt, batch)
        if i % 10 == 0 or i == args.steps - 1:
            loss = float(metrics["nll"])
            tok_s = args.batch * args.seq / max(time.time() - t_step, 1e-9)
            print(f"step {i:4d}  nll {loss:6.3f}  {tok_s/1e3:7.1f}k tok/s")
        wd.report(0, i, time.time() - t_step)
        if args.ckpt_every and i and i % args.ckpt_every == 0:
            mgr.save(i, {"params": params})
    mgr.save(args.steps, {"params": params}, blocking=True)
    it.close()
    print(f"done in {time.time()-t0:.1f}s; checkpoints at {args.ckpt_dir} "
          f"(restore with CheckpointManager.restore — elastic across meshes)")


if __name__ == "__main__":
    main()
