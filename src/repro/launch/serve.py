"""Serving launcher: batched inference behind the Engine protocol.

Three engines, one serving stack (microbatcher + signature-keyed result
cache + active-learning feedback):

    # surrogate: serve a trained FEM surrogate on catalog scenarios
    PYTHONPATH=src python -m repro.launch.serve --engine surrogate \
        --ckpt ckpt/surrogate --scenario ricker-soft-basin \
        --scenario chirp-stiff-shelf --repeat 2 \
        --feedback-out fb.jsonl [--shard --host-devices 4]

    # trajectory: full response histories in one O(log T) associative-scan
    # forward pass (checkpoint from surrogate.trajectory.save_trajectory)
    PYTHONPATH=src python -m repro.launch.serve --engine trajectory \
        --ckpt ckpt/trajectory --scenario ricker-soft-basin --repeat 2

    # decode: batched LLM generation, resident or host-offloaded KV
    PYTHONPATH=src python -m repro.launch.serve --engine decode \
        --arch granite-8b --reduced --batch 4 --new 16 \
        [--offload-kv --npart 4] [--temperature 0.8]

Surrogate requests are keyed by :meth:`Scenario.signature` — a repeated
scenario (``--repeat``) is answered from the result cache without touching
the accelerator.  With ``--feedback-out``, requests whose ensemble
disagreement exceeds ``--feedback-threshold`` are appended as scenario
records; ``repro.launch.campaign --scenarios <file>`` consumes them as a
new data-generation sweep (the active-learning loop).

The KV-offload decode path is Algorithm 3 with the layer-group attention
as the streamed kernel, now engine-internal (`serving/engine.DecodeEngine`).

Reliability knobs (docs/serving.md "Reliability"): ``--deadline-ms`` fails
stale requests instead of batching them, ``--breaker-threshold`` /
``--breaker-cooldown-s`` arm the consecutive-failure circuit breaker, and
``--inject fail_infer_every_n=N,limit=K`` deterministically rehearses the
whole degradation path (split-retry isolation, breaker trip and heal) —
the CI chaos-smoke's serving leg.
"""
import argparse
import os
import sys


def _early_args():
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--host-devices", type=int, default=0)
    args, _ = ap.parse_known_args()
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}"
        )


_early_args()

import numpy as np  # noqa: E402


def _build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="surrogate",
                    choices=["surrogate", "trajectory", "decode"])
    # serving stack
    ap.add_argument("--max-batch", type=int, default=8,
                    help="flush a microbatch once this many rows are pending")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="latency floor: flush when the oldest request has "
                         "waited this long")
    ap.add_argument("--cache-size", type=int, default=256,
                    help="result-cache capacity (entries); 0 disables")
    ap.add_argument("--feedback-out", default=None,
                    help="append high-uncertainty scenarios to this JSONL "
                         "(consumed by campaign --scenarios)")
    ap.add_argument("--feedback-threshold", type=float, default=0.05,
                    help="ensemble-disagreement score above which a request "
                         "is routed to --feedback-out")
    ap.add_argument("--repeat", type=int, default=1,
                    help="submit the workload this many times (round ≥ 2 "
                         "demonstrates cache hits)")
    # reliability knobs (docs/serving.md "Reliability")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline: a request older than this at "
                         "flush time fails with DeadlineExceededError")
    ap.add_argument("--breaker-threshold", type=int, default=0,
                    help="consecutive engine failures that open the circuit "
                         "breaker (0 disables)")
    ap.add_argument("--breaker-cooldown-s", type=float, default=1.0,
                    help="seconds the open breaker rejects requests before "
                         "its half-open probe")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="deterministic fault injection (repro.core.faults): "
                         "'fail_infer_every_n=N[,limit=K]' wraps the engine "
                         "so every Nth infer raises (at most K times) — the "
                         "chaos-smoke rehearsal knob for the breaker/"
                         "split-retry machinery")
    ap.add_argument("--shard", action="store_true",
                    help="shard the batch axis over all devices "
                         "(ShardedEngine on the case mesh)")
    ap.add_argument("--host-devices", type=int, default=0)
    # surrogate workload
    ap.add_argument("--ckpt", default=None,
                    help="surrogate checkpoint dir (surrogate.train."
                         "save_surrogate)")
    ap.add_argument("--scenario", action="append", default=[],
                    help="catalog scenario to serve (repeatable)")
    ap.add_argument("--sweep", default=None,
                    help="scenario sweep spec (JSON file or inline) to serve")
    # decode workload
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode: number of single-prompt requests")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--offload-kv", action="store_true")
    ap.add_argument("--npart", type=int, default=2)
    ap.add_argument("--kv-schedule", default="serial",
                    choices=["serial", "prefetch", "donate"])
    ap.add_argument("--kv-prefetch", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 = seeded categorical sampling")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _stack(args, engine):
    """Engine → (batcher, cache, feedback) per the CLI serving flags."""
    from repro.serving import FeedbackLog, MicroBatcher, ResultCache, ShardedEngine

    if args.shard:
        engine = ShardedEngine(engine)
        print(f"[serve] sharding batch axis over {engine.n_devices} device(s)")
    from repro.core import faults

    inject = faults.parse(args.inject)
    if inject is not None:
        engine = faults.wrap_engine(inject, engine)
        print(f"[serve] [inject] {inject.describe()} — "
              f"signature={engine.signature()}")
    engine.warmup()
    cache = ResultCache(args.cache_size) if args.cache_size > 0 else None
    feedback = (
        FeedbackLog(args.feedback_out, threshold=args.feedback_threshold)
        if args.feedback_out else None
    )
    batcher = MicroBatcher(
        engine, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        cache=cache, feedback=feedback,
        deadline_ms=args.deadline_ms,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown_s,
    )
    return batcher, cache, feedback


def _report(batcher, cache, feedback):
    st = batcher.stats()
    print(f"[serve] requests={st['requests']} rows={st['rows']} "
          f"batches={st['batches']} (full={st['flush_full']} "
          f"timeout={st['flush_timeout']} drain={st['flush_drain']}) "
          f"cache_hits={st['cache_hits']}")
    print(f"[serve] wait mean={st['wait_ms_mean']:.2f}ms "
          f"max={st['wait_ms_max']:.2f}ms  "
          f"infer mean={st['infer_ms_mean']:.1f}ms/batch")
    print(f"[serve] health: engine_failures={st['engine_failures']} "
          f"split_retries={st['split_retries']} "
          f"poison_requests={st['poison_requests']} "
          f"nonfinite_outputs={st['nonfinite_outputs']} "
          f"deadline_expired={st['deadline_expired']} "
          f"breaker_trips={st['breaker_trips']} "
          f"breaker_rejected={st['breaker_rejected']} "
          f"breaker_state={st['breaker_state']}")
    if cache is not None:
        cs = cache.stats()
        print(f"[serve] cache: {cs['size']}/{cs['capacity']} entries, "
              f"{cs['hits']} hit(s), {cs['misses']} miss(es), "
              f"{cs['evictions']} eviction(s)")
    if feedback is not None:
        fs = feedback.stats()
        print(f"[serve] feedback: {fs['routed']}/{fs['observed']} request(s) "
              f"routed to {fs['path']} (threshold {fs['threshold']})")


def _serve_surrogate(args) -> int:
    """--engine surrogate / trajectory: both families serve catalog
    scenarios through the same workload loop — only the engine class (and
    hence the checkpoint format and output stride) differs."""
    from repro import scenario as sc
    from repro.serving import SurrogateEngine, TrajectoryEngine, feedback_plan

    if not args.ckpt:
        print(f"[serve] --engine {args.engine} needs --ckpt", file=sys.stderr)
        return 2
    if args.sweep:
        scenarios = sc.expand(sc.sweep_from_json(args.sweep))
    else:
        names = args.scenario or ["ricker-soft-basin"]
        scenarios = [sc.get(n) for n in names]
    nts = {s.nt for s in scenarios}
    if len(nts) > 1:
        print(f"[serve] scenarios disagree on nt ({sorted(nts)}); "
              f"serve them separately", file=sys.stderr)
        return 2

    cls = TrajectoryEngine if args.engine == "trajectory" else SurrogateEngine
    engine = cls.from_checkpoint(
        args.ckpt, buckets=(args.max_batch,), nt=nts.pop())
    print(f"[serve] {args.engine} step={engine.step} "
          f"members={len(engine.members)} scale={engine.scale:.3g} "
          f"signature={engine.signature()}")

    batcher, cache, feedback = _stack(args, engine)
    with batcher:
        for rnd in range(args.repeat):
            futs = [
                (s, batcher.submit(s.signature(),
                                   s.waves().astype(np.float32), meta=s))
                for s in scenarios
            ]
            for s, f in futs:
                # a failed request degrades (prints) instead of killing the
                # serving loop — poison isolation / breaker rehearsal path
                try:
                    r = f.result()
                except Exception as e:  # noqa: BLE001
                    print(f"[serve] round {rnd + 1} {s.name}: FAILED "
                          f"({type(e).__name__}: {e})")
                    continue
                src = "cache" if r.cached else f"compute {r.infer_ms:.1f}ms"
                print(f"[serve] round {rnd + 1} {s.name}: "
                      f"y{tuple(r.y.shape)} score={r.score:.3f} [{src}]")
            if batcher.stats()["breaker_state"] == "open":
                import time as _time

                print(f"[serve] circuit breaker open — waiting "
                      f"{batcher.breaker_cooldown_s:.1f}s cooldown before "
                      f"next round")
                _time.sleep(batcher.breaker_cooldown_s + 0.05)
        _report(batcher, cache, feedback)

    if feedback is not None and feedback.stats()["routed"] > 0:
        plan = feedback_plan(args.feedback_out)
        print(f"[serve] feedback plan: {plan.n_scenarios} scenario(s) in "
              f"{len(plan.groups)} compile group(s) — run with\n"
              f"        python -m repro.launch.campaign --scenarios "
              f"{args.feedback_out} --out shards/feedback")
    return 0


def _serve_decode(args) -> int:
    import time

    import jax

    from repro.configs import ARCHS
    from repro.models import transformer as T
    from repro.serving import DecodeEngine, ServeConfig
    
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    scfg = ServeConfig(
        kv_offload=args.offload_kv, kv_npart=args.npart,
        temperature=args.temperature, seed=args.seed,
    )
    params, _ = T.init_params(cfg, jax.random.key(0))
    engine = DecodeEngine(
        cfg, params, n_new=args.new, prompt_len=args.prompt_len,
        serve=scfg, buckets=(args.max_batch,),
        kv_schedule=args.kv_schedule, kv_prefetch=args.kv_prefetch,
    )
    print(f"[serve] decode arch={args.arch} "
          f"[KV {'host-offloaded, %d blocks' % args.npart if args.offload_kv else 'resident'}] "
          f"{'greedy' if args.temperature == 0 else f'T={args.temperature}'} "
          f"signature={engine.signature()}")

    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab_size,
    ), np.int32)
    batcher, cache, feedback = _stack(args, engine)
    t0 = time.time()
    with batcher:
        for rnd in range(args.repeat):
            futs = [batcher.submit(f"prompt{i}", prompts[i:i + 1])
                    for i in range(args.batch)]
            outs = [f.result() for f in futs]
        dt = time.time() - t0
        toks = np.concatenate([r.y for r in outs], axis=0)
        print(f"[serve] generated {args.new} × batch {args.batch} in {dt:.1f}s "
              f"({args.new * args.batch / dt:.1f} tok/s)")
        print("[serve] sample:", toks[0][:16].tolist())
        _report(batcher, cache, feedback)
    return 0


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    if args.engine in ("surrogate", "trajectory"):
        return _serve_surrogate(args)
    return _serve_decode(args)


if __name__ == "__main__":
    from repro.launch.bootstrap import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
