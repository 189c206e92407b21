"""The comparison that decides ``correct``.

Every number is compared with its own limit from the cell's file
(``bench/workloads/<cell>.json``); PERF.md gives the readings each limit
was set from.  The step compared is one the timed entry produced, at the
timed sizes, for every case of the cell (``reference/fem_ref.check_step``).
"""
from __future__ import annotations

import concurrent.futures as cf

import numpy as np

ORDER = ("residual", "newmark", "theta", "flags", "stress", "unhealthy", "misplaced")


def misplaced(leaves, want: str) -> int:
    """Spring-state leaves that are not in the memory the config states."""
    return sum(1 for x in leaves if getattr(x.sharding, "memory_kind", None) != want)


def compare(cfg, coords, conn, mat_id, s0, s1, f_t, obs, iters, health,
            placement) -> dict:
    from reference import fem_ref

    tables = fem_ref.build_tables(cfg, coords, conn, mat_id)
    with cf.ThreadPoolExecutor(len(s0)) as ex:  # numpy releases the GIL
        per_case = list(ex.map(
            lambda i: fem_ref.check_step(tables, s0[i], s1[i], f_t[i], obs),
            range(len(s0))))
    out = {k: max(float(p[k]) for p in per_case) for k in per_case[0]}
    out["unhealthy"] = int(np.count_nonzero(health)) + int(
        sum(1 for i in iters if i >= cfg["maxiter"]))
    out["misplaced"] = int(placement)
    return {k: out[k] for k in ORDER}


def judge(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """(all within their limits, one line per number).  A number that is
    not finite fails."""
    ok, lines = True, []
    for k, v in numbers.items():
        lim = limits[k]
        good = bool(np.isfinite(v) and v <= lim)
        ok &= good
        lines.append(f"check {k}: {v!r} (limit {lim!r}) {'ok' if good else 'FAIL'}")
    return ok, lines
