#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data found by name from ``BENCHMARK.json``:
the cell (``workloads`` there and ``bench/workloads/<cell>.json``), its
configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<traffic>.json``), its entry driver
(``bench/drivers/<driver>.py``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).

A run: build the mesh and the program's operators, warm up with one call
(which compiles, or loads from the persistent cache), then call the entry
one step at a time for ``--seconds``; no call starts after that.  After the
window: read the device's peak memory, snapshot the state, make one more
call of the same compiled entry, snapshot again, free the device state, and
check that step against the float64 reference (``bench/reference``).  With
``--trace 1`` the window runs under the profiler and the per-layer metrics
are read from its trace.  Without a TPU it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import checks, traffic  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry in BENCHMARK.json with its files loaded."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(ROOT, configs[w["config"]]["file"])
    mine = lambda ms: [m for m in ms if name in m.get("workloads", [name])]
    return {"bench": w, "config": cfg,
            "cell": load_json(BENCH, "workloads", f"{name}.json"),
            "traffic": load_json(BENCH, "traffic", f"{w['traffic']}.json"),
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def require_chips(jax, chips: int):
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind!r} count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {d.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")


def span_factory(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(name)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, cell_override=None, fault=None) -> dict:
    """One run of a cell; returns the result line as a dict.  The tests
    call it with ``require_tpu=False`` on a small configuration
    (``cell_override``) and with ``fault`` breaking the timed path."""
    c = load_cell(name)
    if cell_override:
        c = cell_override(c)
    cfg, cell = c["config"], c["cell"]
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit("the program (src/repro) is not in this checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.bootstrap import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    from harness.clock import CompileClock

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    if require_tpu:
        require_chips(jax, int(c["bench"]["chips"]))
    seed = int(seed) % (1 << 63)
    waves, offset = traffic.window_waves(c["traffic"], int(cfg["cases"]), cfg["dt"],
                                         int(cfg["record_steps"]), seed)
    log(f"cell {name}: config {cfg['name']}, seed {seed}, record offset "
        f"{offset}, compile cache {cache}")

    from harness import program

    t = time.perf_counter()
    mesh = program.make_mesh(cfg)
    t_mesh = time.perf_counter() - t
    drv_mod = importlib.import_module(f"drivers.{cell['driver']}")
    t = time.perf_counter()
    drv = drv_mod.Driver(cfg, cell, mesh, waves)
    t_build = time.perf_counter() - t
    if fault:
        fault(drv)
    mark = clock.mark()
    t = time.perf_counter()
    warm_iters = drv.call()
    t_warm = time.perf_counter() - t
    c_setup, n_setup = clock.since(mark)
    placement = checks.misplaced(drv.theta_leaves(), cfg["theta_memory"])
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s!r} s: mesh {t_mesh!r} s, operators and carry "
        f"{t_build!r} s, warm-up call {t_warm!r} s (compile {c_setup!r} s, "
        f"{n_setup} program(s)); kernels {drv.backend}")

    span = span_factory(trace)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    iters, calls = [], 0
    mark = clock.mark()
    with (jax.profiler.trace(tdir) if trace else contextlib.nullcontext()):
        with span("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds and drv.t < waves.shape[1] - 1:
                with span("bench.call"):
                    iters.extend(drv.call(span).tolist())
                calls += 1
            window_s = time.perf_counter() - t0
    c_win, n_win = clock.since(mark)
    if n_win:
        raise SystemExit(f"{n_win} program(s) compiled inside the window "
                         f"({c_win!r} s)")
    case_steps = calls * drv.cases
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    placement += checks.misplaced(drv.theta_leaves(), cfg["theta_memory"])
    from work import host_link

    link_bytes = host_link.bytes_per_step(drv.theta_leaves())
    log(f"window {window_s!r} s: {calls} call(s), {case_steps} case-step(s), "
        f"{case_steps / window_s!r} case-steps/s; CG iterations {iters}; "
        f"device peak_bytes_in_use {peak} of {stats.get('bytes_limit')}")

    # ---- the check: one more call of the same compiled entry ---------------
    t = time.perf_counter()
    f_t = waves[:, drv.t]
    s0 = drv.snapshot()
    check_iters = drv.call()
    s1 = drv.snapshot()
    health = drv.health()
    coords, conn, mat_id = mesh.coords, mesh.conn, mesh.mat_id
    drv.free()
    del drv
    numbers = checks.compare(cfg, coords, conn, mat_id, s0, s1, f_t,
                             program.observed_nodes(mesh),
                             iters + warm_iters.tolist() + check_iters.tolist(),
                             health, placement)
    limits = cell["limits"]
    correct, lines = checks.judge(numbers, limits)
    log(f"check {time.perf_counter() - t!r} s")
    failed = int(sum(1 for i in iters if i >= cfg["maxiter"]))

    out = {"correct": bool(correct), "attempted": case_steps, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        from harness import trace as tr_mod

        ctx = types.SimpleNamespace(
            config=cfg, cases=int(cfg["cases"]), case_steps=case_steps,
            steps=calls, iters=iters, device_kind=dev.device_kind,
            host_link_bytes=link_bytes, notes={})
        paths = [os.path.join(r, f) for r, _, fs in os.walk(tdir) for f in fs
                 if f.endswith(".xplane.pb")]
        t = time.perf_counter()
        ctx.trace = tr_mod.extract(paths[0])
        busy_s, traced_s = tr_mod.busy_idle(ctx.trace)
        metrics = {}
        for m in c["per_layer"]:
            v = importlib.import_module(f"metrics.{m['name']}").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=busy_s, window_s=traced_s)
        out["breakdown"] = tr_mod.breakdown(ctx.trace)
        log(f"trace: {len(ctx.trace.ops)} device op(s) on {ctx.trace.device}, "
            f"lines {ctx.trace.lines}, read in {time.perf_counter() - t!r} s; "
            f"{ctx.notes}")
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        taken = {"case_steps_per_s": case_steps / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": taken[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    out.update(metrics=metrics, device=device, checks=numbers_with(numbers, limits))
    for line in lines:
        log(line)
    return out


def numbers_with(numbers: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    out = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
