"""Entry: the campaign's jitted chunk (``campaign.runner.make_campaign_chunk``),
called as ``run_campaign`` calls it between checkpoints: a ``[B, ct, 3]``
slice of the waves in, the observations and iteration counts fetched to the
host after every call.  ``ct`` = 1: each call advances every case one step.
No checkpoint is written."""
from __future__ import annotations

import contextlib

import numpy as np

from harness import program

THETA_KEYS = ("gamma_rev", "tau_rev", "gamma_prev", "gamma_max", "direction", "virgin")


class Driver:
    def __init__(self, cfg: dict, cell: dict, mesh, waves: np.ndarray):
        import jax

        from repro.campaign.runner import make_campaign_chunk
        from repro.core.stream import broadcast_kset
        from repro.fem import backend

        self.jax = jax
        self.cfg, self.mesh = cfg, mesh
        self.cases = int(cfg["cases"])
        self.waves = np.asarray(waves, np.float32)
        self.obs = program.observed_nodes(mesh)
        self.sim = program.sim_config(cfg)
        self.ops = backend.make_operators(mesh, self.sim)
        self.backend = self.ops.kernel_backend.describe()
        self.fn, carry0 = make_campaign_chunk(self.ops, cfg["method"], self.obs)
        self.carry = broadcast_kset(carry0, self.cases)
        # commit every leaf where it lies, as the entry's outputs are, so
        # that the second call finds the first call's program
        self.carry = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, x.sharding), self.carry)
        self.guarded = bool(self.sim.health)
        self.t = 0
        self.last_vel = None

    def call(self, span=None) -> np.ndarray:
        """One step of every case; returns the iterations ``[B]``."""
        span = span or (lambda name: contextlib.nullcontext())
        w = self.waves[:, self.t:self.t + 1]
        self.carry, (vel, iters) = self.fn(self.carry, w)
        with span("bench.fetch"):
            vel, iters = self.jax.device_get((vel, iters))
        self.last_vel = np.asarray(vel)[:, 0]
        self.t += 1
        return np.asarray(iters)[:, 0]

    def _inner(self):
        return self.carry[0] if self.guarded else self.carry

    def theta_leaves(self):
        return self.jax.tree_util.tree_leaves(self._inner()[1])

    def health(self) -> np.ndarray:
        if not self.guarded:
            return np.zeros(self.cases, np.int64)
        return np.asarray(self.jax.device_get(self.carry[1])).astype(np.int64)

    def snapshot(self) -> list[dict]:
        nm, springs, D, alpha, beta_e, *tail = self.jax.device_get(self._inner())
        out = []
        for b in range(self.cases):
            s = {"u": nm.u[b], "v": nm.v[b], "a": nm.a[b], "q": nm.q[b],
                 "D": np.asarray(D[b]).reshape(-1, 6, 6),
                 "alpha": alpha[b], "beta_e": beta_e[b],
                 "du": program.unflat(tail[0][b]) if tail else None}
            s.update({k: springs[k][b] for k in THETA_KEYS})
            if self.last_vel is not None:
                s["vel_obs"] = self.last_vel[b]
            out.append(s)
        return out

    def free(self):
        self.carry = None
