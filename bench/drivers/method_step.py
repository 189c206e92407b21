"""Entry: one method's step (``methods.make_step``) from
``methods.initial_carry``, under one jit with its observation, composed as
``methods.run`` composes it; each call advances the one case a step.  With
``theta_memory`` = ``pinned_host`` the spring state lives in host memory in
``npart`` blocks and streams through the device every step (Algorithm 3);
with ``device`` the same blocks stay in HBM.

The carry is donated to the call, so each step writes its state into the
buffers the last step wrote, as the carry of ``methods.run``'s scan does:
the host-memory blocks are mapped for DMA once, not once per step."""
from __future__ import annotations

import contextlib
import functools

import numpy as np

from harness import program

THETA_KEYS = ("gamma_rev", "tau_rev", "gamma_prev", "gamma_max", "direction", "virgin")


class Driver:
    def __init__(self, cfg: dict, cell: dict, mesh, waves: np.ndarray):
        import jax
        import jax.numpy as jnp

        from repro.fem import backend, methods

        if int(cfg["cases"]) != 1:
            raise SystemExit("method_step drives one case")
        self.jax = jax
        self.cfg, self.mesh = cfg, mesh
        self.cases = 1
        self.waves = np.asarray(waves, np.float32)
        self.obs = program.observed_nodes(mesh)
        self.sim = program.sim_config(cfg)
        self.ops = backend.make_operators(mesh, self.sim)
        self.backend = self.ops.kernel_backend.describe()
        offload = cfg["theta_memory"] == "pinned_host"
        step, streamed = methods.make_step(cfg["method"], self.ops, offload=offload)
        if not streamed:
            raise SystemExit(f"{cfg['method']} does not stream its springs")
        self.carry = methods.initial_carry(
            self.ops, streamed=True, host=offload, ebe=cfg["method"] == "proposed2")
        # commit every leaf where it lies, as the entry's outputs are, so
        # that the second call finds the first call's program; each leaf a
        # buffer of its own, since the call takes them all over
        self.carry = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, x.sharding, may_alias=False), self.carry)
        obs_idx = jnp.asarray(self.obs)
        # a runtime whose jit outputs cannot live in host memory (the CPU)
        # gets them back there eagerly, as the program's hetmem documents;
        # its device outputs cannot take over host inputs, so nothing is
        # donated there
        from repro.core import hetmem

        self.hetmem = hetmem
        self.repin = offload and not hetmem.outputs_can_pin_host()

        @functools.partial(jax.jit, donate_argnums=() if self.repin else 0)
        def step_obs(carry, f_t):
            carry, aux = step(carry, f_t)
            return carry, (aux, carry[0].v[obs_idx])

        self.fn = step_obs
        self.t = 0
        self.last_vel = None
        self.nonconverged = 0

    def call(self, span=None) -> np.ndarray:
        span = span or (lambda name: contextlib.nullcontext())
        self.carry, (aux, vel) = self.fn(self.carry, self.waves[0, self.t])
        if self.repin:
            self.carry = (self.carry[0], self.hetmem.repin_state_to_host(self.carry[1]),
                          *self.carry[2:])
        with span("bench.fetch"):
            aux, vel = self.jax.device_get((aux, vel))
        self.last_vel = np.asarray(vel)[None]
        self.t += 1
        if not bool(aux.converged):
            self.nonconverged += 1
        return np.asarray(aux.iters).reshape(1)

    def theta_leaves(self):
        return self.jax.tree_util.tree_leaves(self.carry[1])

    def health(self) -> np.ndarray:
        return np.array([self.nonconverged], np.int64)

    def snapshot(self) -> list[dict]:
        # copies: the next call takes over the carry's buffers
        nm, ps, D, alpha, beta_e, *tail = self.jax.tree_util.tree_map(
            np.array, self.jax.device_get(self.carry))
        s = {"u": nm.u, "v": nm.v, "a": nm.a, "q": nm.q,
             "D": np.asarray(D).reshape(-1, 6, 6), "alpha": alpha,
             "beta_e": beta_e, "du": program.unflat(tail[0]) if tail else None}
        s.update({k: np.concatenate([blk[i] for blk in ps.blocks])
                  for i, k in enumerate(THETA_KEYS)})
        if self.last_vel is not None:
            s["vel_obs"] = self.last_vel[0]
        return [s]

    def free(self):
        self.carry = None
