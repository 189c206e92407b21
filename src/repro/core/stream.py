"""StreamEngine: the one executor for Algorithm-3 block streaming.

Every workload in this repo that streams host-resident state through the
accelerator — the FEM multi-spring update, the offloaded AdamW step, the
layer-group KV-cache decode, and ensemble dataset generation — is the same
loop: copy block ``j`` host→device, run a per-block kernel, copy the evolved
block back, and overlap block ``j±1``'s transfer with block ``j``'s compute.
This module replaces the four bespoke copies of that loop with a declarative
:class:`StreamPlan` plus a :class:`StreamEngine` executor.

Schedules
---------
``serial``
    Today's semantics and the test invariant: transfer-in → compute →
    transfer-out per block, in trace order.  With ``offload=False`` it is
    bit-identical to the resident computation.  On TPU, XLA's latency-hiding
    scheduler still discovers the double-buffer overlap from the unrolled
    chain (see core/hetmem.py).
``prefetch`` (depth ``k`` ≥ 1)
    Issues block ``j+k``'s host→device copy *before* block ``j``'s compute in
    trace order, so the overlap of Algorithm 3 is explicit in the program
    rather than recovered by the scheduler.  ``k`` device copies are in
    flight at once → ``k+1`` device-resident blocks (``k=1`` is the paper's
    double buffer).  Numerically identical to ``serial``.
``donate``
    The paper's GPU realization: exactly two device buffers, block ``j``'s
    device buffer donated to its own output.  Realized with a per-block
    jitted call carrying ``donate_argnums=(0,)`` (eager engine use only —
    under an outer trace we fall back to ``prefetch(1)`` ordering, where
    XLA's liveness analysis enforces the same two-buffer bound).

k-set ensembles (generalized 2SET)
----------------------------------
``kset=k`` declares a leading ensemble axis of size ``k`` on every block and
per-block input: the per-block kernel is written for one ensemble member and
the engine vmaps it across members, so one streamed pass advances ``k``
independent ensemble members per block.  This generalizes the paper's
Proposed Method 2 "2SET" residency (two problem sets batched through the
memory freed by EBE) to any ``k``, and to the streamed regime.  ``broadcast``
inputs stay unmapped (shared across members) — exactly the amortization that
makes 2SET profitable: the per-member transfer shrinks while shared operands
are fetched once.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import hetmem
from repro.core.hetmem import PartitionedState

SCHEDULES = ("serial", "prefetch", "donate")


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Declarative description of one streamed pass (Algorithm 3).

    ``npart``       number of host-resident blocks (must match the state).
    ``schedule``    "serial" | "prefetch" | "donate" (see module docstring).
    ``prefetch``    copy-ahead depth for the "prefetch" schedule.
    ``offload``     False elides every transfer — semantics invariant.
    ``collect``     per-block kernel returns an extra device-resident output
                    (the paper's tangent stiffness ``D_j``) gathered into a
                    list instead of round-tripping to host.
    ``kset``        ensemble members batched per block (1 = no ensemble axis).
    ``device_kind`` / ``host_kind``   memory kinds for the two sides.
    ``donate``      allow buffer donation in the "donate" schedule.
    """

    npart: int
    schedule: str = "serial"
    prefetch: int = 1
    offload: bool = True
    collect: bool = False
    kset: int = 1
    device_kind: str = hetmem.DEVICE
    host_kind: str = hetmem.HOST
    donate: bool = True

    def __post_init__(self):
        if self.npart < 1:
            raise ValueError(f"npart must be ≥ 1, got {self.npart}")
        if self.schedule not in SCHEDULES:
            raise ValueError(f"schedule {self.schedule!r} not in {SCHEDULES}")
        if self.prefetch < 1:
            raise ValueError(f"prefetch depth must be ≥ 1, got {self.prefetch}")
        if self.kset < 1:
            raise ValueError(f"kset must be ≥ 1, got {self.kset}")

    @property
    def device_buffers(self) -> int:
        """Device-resident block count implied by the schedule."""
        if not self.offload:
            return self.npart  # resident regime: everything on device
        if self.schedule == "prefetch":
            return self.prefetch + 1
        return 2  # serial / donate: the paper's double buffer


class StreamResult(NamedTuple):
    state: PartitionedState
    carry: Any
    extras: list


class StreamEngine:
    """Executes a :class:`StreamPlan` over a :class:`PartitionedState`.

    The per-block kernel ``fn`` sees device-resident operands and returns the
    evolved block (plus optionally a carried value and/or a collected extra):

    ==============================  =========================================
    plan                            ``fn`` signature → return
    ==============================  =========================================
    plain                           ``fn(blk, *pb_j, *bc) → blk'``
    ``collect=True``                ``… → (blk', extra)``
    ``carry=…`` passed to ``run``   ``fn(blk, carry, *pb_j, *bc) → (blk', carry')``
    carry + collect                 ``… → (blk', carry', extra)``
    ==============================  =========================================

    A carry threads sequentially through the blocks (the serving decode's
    hidden state flowing through layer groups); it does not impede prefetch,
    because transfers depend only on the host blocks.
    """

    def __init__(self, plan: StreamPlan):
        self.plan = plan
        self._jit_cache: dict = {}  # (fn, has_carry) → jitted donate-mode call

    # -- transfers ----------------------------------------------------------
    def _h2d(self, tree: Any) -> Any:
        return hetmem.transfer(tree, self.plan.device_kind) if self.plan.offload else tree

    def _d2h(self, tree: Any) -> Any:
        return hetmem.transfer(tree, self.plan.host_kind) if self.plan.offload else tree

    # -- per-block call (kset vmap + optional donation) ---------------------
    def _make_call(self, fn: Callable, has_carry: bool, tracing: bool):
        """Build ``call(dev_blk, carry, args, broadcast)`` for this plan.

        ``broadcast`` is an explicit argument (not a closure capture) so the
        donate-mode jitted call can be cached across :meth:`run` invocations
        without staling old broadcast operands.
        """
        plan = self.plan

        if has_carry:
            def call(dev_blk, carry, args, bc):
                return fn(dev_blk, carry, *args, *bc)
        else:
            def call(dev_blk, carry, args, bc):
                del carry
                return fn(dev_blk, *args, *bc)

        if plan.kset > 1:
            axes = (0, 0 if has_carry else None, 0, None)
            call = jax.vmap(call, in_axes=axes)

        if plan.schedule == "donate" and plan.donate and not tracing:
            # Eager engine use: donate the device block's buffer to its own
            # output — exactly two device-resident block buffers, as in the
            # paper's CUDA implementation.  Donation is only requested where
            # the runtime honors it AND the engine owns the buffer via a real
            # host→device copy — donating with elided transfers would
            # invalidate the caller's own state blocks.
            key = (fn, has_carry)
            cached = self._jit_cache.get(key)
            if cached is None:
                import repro.core.hetmem as _hm

                donate = (
                    (0,)
                    if (
                        jax.default_backend() in ("gpu", "tpu")
                        and plan.offload
                        and _hm.transfer_is_real(plan.device_kind)
                    )
                    else ()
                )
                cached = jax.jit(call, donate_argnums=donate)
                self._jit_cache[key] = cached
            call = cached
        return call

    @staticmethod
    def _unpack(out, has_carry: bool, collect: bool):
        if has_carry and collect:
            return out  # (blk', carry', extra)
        if has_carry:
            return out[0], out[1], None
        if collect:
            return out[0], None, out[1]
        return out, None, None

    # -- the streamed loop --------------------------------------------------
    def run(
        self,
        fn: Callable[..., Any],
        state: PartitionedState,
        *,
        per_block: Sequence[Sequence[Any]] = (),
        broadcast: Sequence[Any] = (),
        carry: Any = None,
    ) -> StreamResult:
        plan = self.plan
        blocks = state.blocks
        npart = len(blocks)
        if plan.npart != npart:
            raise ValueError(f"plan.npart={plan.npart} but state has {npart} blocks")
        for i, pb in enumerate(per_block):
            if len(pb) != npart:
                raise ValueError(f"per_block[{i}] has {len(pb)} entries, expected {npart}")
        if plan.kset > 1:
            for j, blk in enumerate(blocks):
                for x in jax.tree_util.tree_leaves(blk):
                    if getattr(x, "ndim", 0) < 1 or x.shape[0] != plan.kset:
                        raise ValueError(
                            f"kset={plan.kset} but block {j} leaf has leading axis "
                            f"{getattr(x, 'shape', ())} — stack members with stack_kset_states"
                        )
        has_carry = carry is not None

        leaves = jax.tree_util.tree_leaves((blocks, tuple(per_block), tuple(broadcast), carry))
        tracing = any(isinstance(x, jax.core.Tracer) for x in leaves)
        call = self._make_call(fn, has_carry, tracing)
        bc = tuple(broadcast)

        # Copy-ahead depth: "prefetch" uses the configured depth; "donate"
        # still double-buffers (depth 1) so block j+1's copy-in overlaps
        # block j's compute; "serial" keeps strict in-order transfers.
        depth = 0
        if plan.offload and plan.schedule != "serial":
            depth = max(1, plan.prefetch) if plan.schedule == "prefetch" else 1

        dev: list[Any] = [self._h2d(blocks[j]) for j in range(min(depth, npart))]
        out_blocks: list[Any] = []
        extras: list[Any] = []
        for j in range(npart):
            if depth:
                nxt = j + depth
                if nxt < npart:
                    dev.append(self._h2d(blocks[nxt]))
                dev_blk, dev[j] = dev[j], None  # drop ref → bounded liveness
            else:
                dev_blk = self._h2d(blocks[j])
            # the block's compute in a named scope of its own, so a device
            # trace attributes it to the block
            with jax.named_scope(f"repro.stream_block{j}"):
                args = tuple(pb[j] for pb in per_block)
                out = call(dev_blk, carry, args, bc)
                new_blk, carry, extra = self._unpack(out, has_carry, plan.collect)
            if plan.collect:
                extras.append(extra)
            out_blocks.append(self._d2h(new_blk))
        new_state = PartitionedState(blocks=out_blocks, spec=state.spec)
        return StreamResult(state=new_state, carry=carry, extras=extras)

    # -- device-resident k-set map (Alg. 4 / 2SET) --------------------------
    def kmap(self, fn: Callable[..., Any], *mapped: Any, broadcast: Sequence[Any] = ()):
        """Batch ``kset`` ensemble members through one device residency.

        ``mapped`` pytrees carry the leading k-set axis; ``broadcast`` args
        are shared across members.  This is the device-resident limit of the
        plan (``npart=1``, no transfers): the paper's 2SET expressed as a
        vmap, centralized here so resident and streamed ensembles share one
        definition of the ensemble axis.
        """
        k = self.plan.kset
        for x in jax.tree_util.tree_leaves(tuple(mapped)):
            if getattr(x, "ndim", 0) < 1 or x.shape[0] != k:
                raise ValueError(
                    f"k-set leading axis {getattr(x, 'shape', ())} != kset={k}"
                )
        axes = (0,) * len(mapped) + (None,) * len(broadcast)
        return jax.vmap(lambda *a: fn(*a), in_axes=axes)(*mapped, *broadcast)


# ---------------------------------------------------------------------------
# k-set stacking helpers
# ---------------------------------------------------------------------------


def stack_kset(trees: Sequence[Any]) -> Any:
    """Stack ``k`` identically-structured pytrees along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def broadcast_kset(tree: Any, k: int) -> Any:
    """Replicate one pytree ``k``-fold along a new leading ensemble axis.

    The materialized form of ``stack_kset([tree] * k)`` — used to seed a
    k-set batch whose members all start from the same initial state (every
    ensemble case begins from the virgin constitutive state)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), tree
    )


def pad_kset(arr, multiple: int, axis: int = 0):
    """Pad ``arr``'s ensemble axis up to a ``multiple`` → ``(padded, valid)``.

    Remainder tolerance for k-set batching: when the case count is not a
    multiple of ``kset × n_devices`` the tail batch is padded with repeats of
    the last case (keeping the padded lanes numerically well-behaved) and
    ``valid`` masks them out — `n_waves % (kset × n_devices)` need not be 0.
    """
    import numpy as np

    n = arr.shape[axis]
    if n == 0:
        raise ValueError("cannot pad an empty ensemble axis")
    pad = (-n) % multiple
    valid = np.arange(n + pad) < n
    if pad == 0:
        return arr, valid
    xp = jnp if isinstance(arr, jnp.ndarray) else np
    idx = [slice(None)] * arr.ndim
    idx[axis] = slice(n - 1, n)
    filler = xp.repeat(arr[tuple(idx)], pad, axis=axis)
    return xp.concatenate([arr, filler], axis=axis), valid


def unstack_kset(tree: Any, k: int) -> list[Any]:
    """Inverse of :func:`stack_kset`."""
    return [jax.tree_util.tree_map(lambda x: x[i], tree) for i in range(k)]


def stack_kset_states(states: Sequence[PartitionedState]) -> PartitionedState:
    """Stack ``k`` identically-partitioned states into one k-set state.

    Every block leaf gains a leading ``k`` axis; stream the result with a
    ``kset=k`` plan to advance all members in one pass.
    """
    spec = states[0].spec
    npart = len(states[0].blocks)
    for s in states[1:]:
        if len(s.blocks) != npart:
            raise ValueError("k-set members must share the block partition")
    blocks = [stack_kset([s.blocks[j] for s in states]) for j in range(npart)]
    return PartitionedState(blocks=blocks, spec=spec)


def unstack_kset_state(state: PartitionedState, k: int) -> list[PartitionedState]:
    """Inverse of :func:`stack_kset_states`."""
    return [
        PartitionedState(
            blocks=[jax.tree_util.tree_map(lambda x: x[i], blk) for blk in state.blocks],
            spec=state.spec,
        )
        for i in range(k)
    ]
