"""Multi-host process topology + coordination-service barriers.

The campaign's multi-host story is deliberately *not* a cross-process SPMD
program: ensemble cases are embarrassingly parallel (DESIGN.md §5), so each
process runs the identical compiled program on the case slice it owns and
the only cross-process traffic is *coordination* — "everyone has written
their checkpoint shard, process 0 may now commit the manifest".  That
coordination rides jax's distributed runtime service (the same service
``jax.distributed.initialize`` brings up), **not** an XLA collective:

* it works on every backend, including CPU test processes, where
  cross-process XLA executables are unimplemented
  (``Multiprocess computations aren't implemented on the CPU backend``);
* a barrier between file writes must not require a device computation in
  the first place — it synchronizes *hosts*, not devices.

``barrier()`` therefore waits on the coordination-service client.
Everything here degrades to a no-op in single-process runs, so callers
never branch on world size.
"""
from __future__ import annotations

import itertools

import jax

_BARRIER_TIMEOUT_MS = 600_000
# Service barrier ids must be unique per synchronization point; processes
# reach the same call sites in the same order (the campaign's control flow
# is deterministic), so a shared monotonic counter keeps ids aligned.
_counter = itertools.count()


def process_index() -> int:
    """This process's rank (0 in single-process runs)."""
    return jax.process_index()


def process_count() -> int:
    """World size (1 when ``jax.distributed`` was never initialized)."""
    return jax.process_count()


def is_distributed() -> bool:
    return process_count() > 1


def barrier(tag: str, *, timeout_ms: int = _BARRIER_TIMEOUT_MS) -> None:
    """Block until every process reaches this barrier; no-op single-process.

    ``tag`` names the synchronization point in service logs/errors; the
    actual barrier id appends a monotonic counter so repeated passes through
    the same call site (one per checkpoint, one per banked round) never
    collide.
    """
    if not is_distributed():
        return
    # JAX exposes no public host barrier; its coordination-service client
    # is the one that works on every backend (see the module docstring)
    from jax._src import distributed as _dist  # noqa: PLC0415

    _dist.global_state.client.wait_at_barrier(f"{tag}_{next(_counter)}", timeout_ms)


def free_port() -> int:
    """An OS-assigned free TCP port for a local coordination service — the
    multi-process test harness and ``campaign_bench --processes N`` both
    bind their coordinator here.  (Bind-then-close has an inherent reuse
    race; acceptable for single-machine rehearsal.)"""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def make_barrier(tag: str):
    """A zero-argument barrier callable bound to ``tag`` — the injection
    point :class:`~repro.training.checkpoint.CheckpointManager` takes so
    unit tests can substitute a no-op without a real service."""
    return lambda: barrier(tag)
