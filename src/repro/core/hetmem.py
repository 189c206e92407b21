"""Heterogeneous memory management (the paper's core contribution), in JAX.

The paper (Ichimura et al. 2026) keeps a huge evolving state array ``θ`` in
*host* memory and streams it through the accelerator in ``npart`` blocks,
double-buffering so the CPU↔GPU transfer of block ``j±1`` overlaps the
compute of block ``j`` (Algorithm 3).  Only two blocks ever reside in
accelerator memory.

TPU-native realization
----------------------
JAX expresses memory placement with sharding ``memory_kind``:

* ``"device"``       → HBM
* ``"pinned_host"``  → host DRAM, DMA-able

:func:`stream_map` emits, for each block, ``device_put(block → device)`` →
``fn`` → ``device_put(out → pinned_host)`` as an *unrolled* chain.  On TPU,
XLA lowers the placements to asynchronous ``copy-start/copy-done`` pairs and
its latency-hiding scheduler overlaps block ``j+1``'s copy-in with block
``j``'s compute — i.e. the double buffer of Algorithm 3 is recovered by the
scheduler rather than hand-rolled CUDA streams.  The GPU version needed
exactly two device-resident buffers; here the liveness analysis of the
scheduler enforces the same bound because each block's device copy dies at
the end of its compute.

Blocks are plain pytrees kept in a Python list (block selection is a
*trace-time* constant), so no slicing of host arrays is ever staged — on a
real TPU a device slice of a host array would force a full copy and defeat
the purpose.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.utils.tree import BlockSpec, group_leaves_into_blocks, reassemble_blocks

DEVICE = "device"
HOST = "pinned_host"


def supported_memory_kinds() -> tuple[str, ...]:
    return tuple(m.kind for m in jax.devices()[0].addressable_memories())


def host_memory_available() -> bool:
    return HOST in supported_memory_kinds()


def with_memory_kind(sharding, kind: str):
    """Return ``sharding`` with its memory kind replaced by ``kind``."""
    return sharding.with_memory_kind(kind)


_SPACE = {DEVICE: jax.memory.Space.Device, HOST: jax.memory.Space.Host}


@functools.lru_cache(maxsize=1)
def _runtime_kinds() -> frozenset:
    return frozenset(supported_memory_kinds())


def transfers_supported() -> bool:
    """True when the runtime has a host memory kind to stream θ through.

    Every backend of the installed JAX (CPU, TPU, GPU) advertises
    ``device`` and ``pinned_host``; the memory space is part of an array's
    type (``float32<host>``), so the placements of Algorithm 3 are real
    copies on all of them.
    """
    return HOST in _runtime_kinds()


def transfer_is_real(kind: str) -> bool:
    """True when :func:`transfer` to ``kind`` stages an actual copy."""
    return kind in _SPACE and kind in _runtime_kinds()


def transfer(tree: Any, kind: str) -> Any:
    """Stage a memory-space transfer for every leaf of ``tree`` inside jit.

    Raises ``ValueError`` for a kind the runtime does not advertise: a
    streamed pass that silently kept θ where it was would hide the very
    placement it exists to make.
    """
    if not transfer_is_real(kind):
        raise ValueError(
            f"memory kind {kind!r} cannot be a transfer target here "
            f"(runtime advertises {sorted(_runtime_kinds())}, transfers "
            f"target {sorted(_SPACE)})"
        )
    space = _SPACE[kind]
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, space), tree)


def to_device(tree: Any) -> Any:
    return transfer(tree, DEVICE)


def to_host(tree: Any) -> Any:
    return transfer(tree, HOST)


def put_host(tree: Any, sharding=None) -> Any:
    """Eagerly place ``tree`` in host memory (outside jit).

    ``sharding`` may be a distributed sharding; defaults to the default
    device's host memory.  Raises ``ValueError`` on a runtime without a
    host memory kind.
    """
    if not host_memory_available():
        raise ValueError(
            f"no {HOST!r} memory on this runtime "
            f"(advertises {list(supported_memory_kinds())})"
        )
    if sharding is None:
        sharding = SingleDeviceSharding(jax.devices()[0], memory_kind=HOST)
    else:
        sharding = with_memory_kind(sharding, HOST)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


@dataclasses.dataclass
class PartitionedState:
    """State of Algorithm 3: ``npart`` host-resident blocks of a pytree.

    ``blocks[j]`` is a list of leaves; ``spec`` reassembles the original
    pytree.  The object itself is a pytree (registered below), so it can be
    passed through jit boundaries; the block list length is static.
    """

    blocks: list[list[Any]]
    spec: BlockSpec = dataclasses.field(metadata={"static": True})

    @property
    def npart(self) -> int:
        return self.spec.npart

    def unpartition(self) -> Any:
        return reassemble_blocks(self.blocks, self.spec)

    @staticmethod
    def partition(tree: Any, npart: int) -> "PartitionedState":
        blocks, spec = group_leaves_into_blocks(tree, npart)
        return PartitionedState(blocks=blocks, spec=spec)


def _ps_flatten(ps: PartitionedState):
    return (ps.blocks,), ps.spec


def _ps_unflatten(spec, children):
    return PartitionedState(blocks=children[0], spec=spec)


jax.tree_util.register_pytree_node(PartitionedState, _ps_flatten, _ps_unflatten)


def stream_blocks(
    fn: Callable[..., Any],
    state: PartitionedState,
    *,
    per_block: Sequence[Sequence[Any]] = (),
    broadcast: Sequence[Any] = (),
    offload: bool = True,
    collect: bool = False,
    schedule: str = "serial",
    prefetch: int = 1,
):
    """Algorithm 3: map ``fn`` over host-resident blocks with streamed I/O.

    ``fn(dev_block, *per_block_j, *broadcast)`` is applied to each block
    after it is copied host→device; its first (or only) return value is the
    new block, copied device→host.  With ``collect=True`` ``fn`` returns
    ``(new_block, extra)`` and the device-resident ``extra``\\s are returned
    as a list — mirroring Algorithm 3 where ``θ_j`` round-trips to host but
    the tangent stiffness ``D_j`` stays on the GPU for the CRS update.

    ``per_block`` are *lists of length npart* of device-resident inputs
    (e.g. this block's gradients); ``broadcast`` are shared device inputs
    (e.g. the solver's ``δu``).  With ``offload=False`` the transfers are
    elided and semantics are unchanged — the invariant the tests assert.

    This is a thin compatibility wrapper over :class:`repro.core.stream.
    StreamEngine` with the ``serial`` schedule (plus ``schedule``/``prefetch``
    pass-throughs for callers that want the explicit-overlap executor).
    """
    from repro.core.stream import StreamEngine, StreamPlan

    plan = StreamPlan(
        npart=len(state.blocks),
        schedule=schedule,
        prefetch=prefetch,
        offload=offload,
        collect=collect,
    )
    res = StreamEngine(plan).run(fn, state, per_block=per_block, broadcast=broadcast)
    return (res.state, res.extras) if collect else res.state


def stream_map(fn, state, *broadcast_args, offload: bool = True):
    return stream_blocks(fn, state, broadcast=broadcast_args, offload=offload)


def stream_map_collect(fn, state, *broadcast_args, offload: bool = True):
    return stream_blocks(fn, state, broadcast=broadcast_args, offload=offload, collect=True)


def check_divisible(n: int, npart: int, what: str = "axis size") -> int:
    """Validate ``npart | n`` and return the chunk size.

    The single divisibility gate for every Algorithm-3 block split: silent
    truncation (``n // npart`` chunks dropping a remainder) corrupts physics
    — trailing quadrature points would simply stop evolving — so all callers
    (:func:`partition_arrays`, ``fem/methods.block_params``,
    ``fem/methods._streamed_multispring``) raise the same error instead.
    """
    if npart < 1:
        raise ValueError(f"npart must be ≥ 1, got {npart}")
    if n % npart != 0:
        raise ValueError(f"{what} {n} not divisible by npart={npart}")
    return n // npart


def partition_arrays(tree: Any, npart: int, axis: int = 0) -> list[Any]:
    """Split every leaf of ``tree`` into ``npart`` equal chunks along ``axis``.

    Used by the FEM side, where the natural block unit is a contiguous range
    of *elements* (all state leaves share the element-count leading axis).
    Leading dim must be divisible by npart (meshgen pads to guarantee this).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[axis]
    chunk = check_divisible(n, npart)

    def take(x, j):
        idx = [slice(None)] * x.ndim
        idx[axis] = slice(j * chunk, (j + 1) * chunk)
        return x[tuple(idx)]

    return [jax.tree_util.tree_map(lambda x: take(x, j), tree) for j in range(npart)]


def concat_blocks(blocks: Sequence[Any], axis: int = 0) -> Any:
    """Inverse of :func:`partition_arrays`."""
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=axis), *blocks)


def named_host_sharding(mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec, memory_kind=HOST)


def named_device_sharding(mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec, memory_kind=DEVICE)


def host_out_shardings(out_shape_tree: Any, sharding=None) -> Any:
    """Pytree of host shardings matching ``jax.eval_shape`` output.

    jit outputs land in device memory unless ``out_shardings`` pins them to
    host; callers that round-trip offloaded state through a jitted step use
    this to keep the state host-resident end-to-end.
    """
    if sharding is None:
        sharding = SingleDeviceSharding(jax.devices()[0], memory_kind=HOST)
    else:
        sharding = with_memory_kind(sharding, HOST)
    return jax.tree_util.tree_map(lambda _: sharding, out_shape_tree)


def outputs_can_pin_host() -> bool:
    """TPU/GPU runtimes materialize host-pinned jit outputs; the CPU runtime
    lacks the ``annotate_device_placement``→Host custom call.  Callers use
    this to fall back to an eager re-pin (:func:`put_host`) after the step —
    semantics identical, only the extra copy differs (CPU-only, test env)."""
    return jax.default_backend() != "cpu"


def repin_state_to_host(state: "PartitionedState") -> "PartitionedState":
    """Eagerly move a (device-resident) streamed state back to host memory."""
    return PartitionedState(
        blocks=[put_host(blk) for blk in state.blocks], spec=state.spec
    )
