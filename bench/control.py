#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --steps 3 \\
        [--control 1] --out <file.jsonl>

For each seed, in one process: build the cell's entry as ``run.py`` does
(on records drawn from that seed, not the traffic's fixed ones),
advance it from rest by one warm-up call and ``--steps`` more, snapshot the
state, make one more call and snapshot again, and compare that step with
the reference (the sound program's readings).  With ``--control 1`` the
reference itself, every stored value rounded to bfloat16
(``reference.fem_ref.control_step``), also computes that step from the same
state and is compared the same way (the control's readings).  One JSON line
per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)

    c = run.load_cell(a.workload)
    cfg, cell = c["config"], c["cell"]
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro.launch.bootstrap import enable_compile_cache

    enable_compile_cache()
    import jax

    run.require_chips(jax, int(c["bench"]["chips"]))
    from harness import checks, program, traffic
    from reference import fem_ref

    mesh = program.make_mesh(cfg)
    obs = program.observed_nodes(mesh)
    tables = fem_ref.build_tables(cfg, mesh.coords, mesh.conn, mesh.mat_id)
    drivers = importlib.import_module(f"drivers.{cell['driver']}")
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    for seed in (int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        # each seed gets records of its own: the readings span inputs, where
        # the benchmark's runs all feed the traffic's fixed records
        mix = dict(c["traffic"], record_seed=seed % (1 << 63))
        waves, _ = traffic.window_waves(mix, int(cfg["cases"]), cfg["dt"],
                                        int(cfg["record_steps"]), seed % (1 << 63))
        drv = drivers.Driver(cfg, cell, mesh, waves)
        iters = []
        for _ in range(1 + a.steps):
            iters += drv.call().tolist()
        f_t = waves[:, drv.t]
        s0 = drv.snapshot()
        iters += drv.call().tolist()
        s1 = drv.snapshot()
        health = drv.health()
        drv.free()
        del drv
        rec = {"seed": seed, "steps": 2 + a.steps, "iters": iters}
        rec["program"] = checks.compare(cfg, mesh.coords, mesh.conn, mesh.mat_id,
                                        s0, s1, f_t, obs, iters, health, 0)
        if a.control:
            ctl = [fem_ref.control_step(tables, s, f_t[i], obs)
                   for i, s in enumerate(s0)]
            rec["control"] = checks.compare(cfg, mesh.coords, mesh.conn,
                                            mesh.mat_id, s0, ctl, f_t, obs,
                                            [], [0] * len(s0), 0)
        rec["seconds"] = time.perf_counter() - t
        line = json.dumps(rec)
        print(line, flush=True)
        with open(a.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
