"""Synthetic data pipeline: deterministic token streams with learnable
structure, background prefetch, and device placement by sharding.

The bigram-chain generator gives the convergence tests something a model can
actually learn (loss must drop below the unigram entropy); the uniform
stream is for pure-throughput benchmarks.  ``Prefetcher`` overlaps host
batch synthesis with device compute.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    kind: str = "bigram"      # bigram | uniform
    seed: int = 0
    n_frontend_tokens: int = 0
    frontend: Optional[str] = None
    d_model: int = 0


def _bigram_table(vocab: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    # each token prefers a handful of successors → learnable structure
    table = rng.dirichlet(np.full(min(vocab, 32), 0.2), size=vocab)
    succ = rng.integers(0, vocab, size=(vocab, min(vocab, 32)))
    return table, succ


def batches(cfg: DataConfig) -> Iterator[dict[str, np.ndarray]]:
    rng = np.random.default_rng(cfg.seed)
    if cfg.kind == "bigram":
        probs, succ = _bigram_table(cfg.vocab_size, cfg.seed + 1)
    step = 0
    while True:
        B, S = cfg.global_batch, cfg.seq_len
        if cfg.kind == "uniform":
            toks = rng.integers(0, cfg.vocab_size, size=(B, S + 1))
        else:
            toks = np.empty((B, S + 1), np.int64)
            toks[:, 0] = rng.integers(0, cfg.vocab_size, size=B)
            for t in range(S):
                p = probs[toks[:, t]]
                choice = (p.cumsum(1) > rng.random((B, 1))).argmax(1)
                toks[:, t + 1] = succ[toks[:, t], choice]
        batch = {
            "tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
        }
        if cfg.frontend:
            batch[ "frames" if cfg.frontend == "audio_frames" else "patches"] = rng.normal(
                size=(B, cfg.n_frontend_tokens, cfg.d_model)
            ).astype(np.float32)
            if cfg.frontend == "vision_patches":
                # patch positions carry no next-token loss
                pad = np.full((B, cfg.n_frontend_tokens), -100, np.int32)
                batch["labels"] = np.concatenate([pad, batch["labels"]], axis=1)
        step += 1
        yield batch


class Prefetcher:
    """Background-thread prefetch + device placement (depth-bounded)."""

    def __init__(self, it: Iterator, depth: int = 2, shardings: Optional[dict] = None):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._it = it
        self._shardings = shardings
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        for item in self._it:
            if self._stop.is_set():
                break
            if self._shardings:
                item = {
                    k: jax.device_put(v, self._shardings.get(k)) if k in self._shardings else jnp.asarray(v)
                    for k, v in item.items()
                }
            self._q.put(item)
        self._q.put(None)

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is None:
            raise StopIteration
        return item

    def close(self):
        self._stop.set()
