#!/usr/bin/env python3
"""Record what a cell's program is made of, on the chip.

    python3 bench/record.py trace --workload <cell> --seed <n> --seconds <s> --out <file>
    python3 bench/record.py hlo --workload <cell> [--workload <cell> ...] --out <prefix>

``trace``: one traced run of the cell, exactly as ``bench/run.py --trace 1``
makes it (its result line is printed), kept as a small recorded trace for
the tests: the device operations and spans of a stretch of the window
around the start of the window's second call (``--at boundary``: the end
of one step and the start of the next) or its middle (``--at middle``:
inside the step's solve), and the instruction → ``op_name`` map of those
operations (``harness.scopes.record``), under ``--max-kb``.

``hlo``: each cell's compiled entry, for the arguments its driver passes,
as optimized HLO text with every ``metadata={...}``, the debug tables and
the Pallas kernels' source locations taken out (``strip_metadata``,
``strip_kernel_locations``), written to ``<prefix>_<cell>.txt``; it
prints the text's SHA-256, and that of the text with its instructions
renumbered in order (``canonical_names``).  Two builds with equal texts
compile to the same program.
"""
from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip_metadata(text: str) -> str:
    """``text`` without its debug tables (the source files, functions and
    stack frames that the metadata points into), without blank lines (a
    build with fewer tables leaves fewer), and with every
    ``, metadata={...}`` removed (quoted strings in it may hold braces)."""
    lines, skip = [], False
    for line in text.split("\n"):
        if line.strip() in DEBUG_TABLES:
            skip = True
        elif skip and (not line.strip() or line.startswith(("%", "ENTRY"))):
            skip = False
        if not skip and line.strip():
            lines.append(line)
    text = "\n".join(lines)
    out, i, key = [], 0, ", metadata={"
    while True:
        j = text.find(key, i)
        if j < 0:
            out.append(text[i:])
            return "".join(out)
        out.append(text[i:j])
        k, depth, quoted = j + len(key), 1, False
        while depth:
            c = text[k]
            if c == "\\" and quoted:
                k += 1
            elif c == '"':
                quoted = not quoted
            elif not quoted:
                depth += {"{": 1, "}": -1}.get(c, 0)
            k += 1
        i = k


def strip_kernel_locations(text: str) -> str:
    """``text`` with each Pallas kernel's serialized MLIR body replaced by
    the SHA-256 of its text without source locations: a kernel carries the
    file, line and scope of every operation it was traced from, which
    change with a named scope (or the checkout's path) and not the code."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation.get_asm(
                enable_debug_info=False)
        return '"body":"sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'"body":"([A-Za-z0-9+/=]+)"', body, text)


def canonical_names(text: str) -> str:
    """``text`` with every ``%name`` replaced by its order of first
    appearance: two programs equal under it differ at most in how their
    instructions and computations are numbered."""
    ids: dict[str, str] = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: "%" + ids.setdefault(m.group(1), f"v{len(ids)}"), text)


def trimmed(tr, max_bytes: int, scope_map: dict, at: str = "boundary") -> str:
    """The recorded trace of the widest stretch that fits ``max_bytes`` as
    JSON around the start (``at="boundary"``) or the middle (``"middle"``)
    of the window's second call."""
    from harness import scopes, trace

    calls = sorted((s[1], s[2]) for s in tr.spans if s[0] == "bench.call")
    lo_w, hi_w = tr.window
    start, dur = calls[1] if len(calls) > 1 else (lo_w, hi_w - lo_w)
    t0, before = (start, 1 / 3) if at == "boundary" else (start + dur / 2, 1 / 2)

    def around(half):
        lo, hi = max(lo_w, t0 - half * before), min(hi_w, t0 + half * (1 - before))
        spans = [["bench.window", lo, hi - lo]] + [
            s for s in trace.clip(tr.spans, lo, hi) if s[0] != "bench.window"]
        return scopes.record(trace.Trace(
            ops=trace.clip(tr.ops, lo, hi), spans=spans, device=tr.device,
            lines=tr.lines, async_ops=trace.clip(tr.async_ops, lo, hi)), scope_map)

    a, b, best = 0.0, float(hi_w - lo_w), None
    for _ in range(30):  # bisect the widest stretch that fits
        text = around((a + b) / 2)
        if len(text) <= max_bytes:
            a, best = (a + b) / 2, text
        else:
            b = (a + b) / 2
    return best


def record_trace(a) -> int:
    import run
    from harness import scopes, trace

    seen = {}
    extract = trace.extract

    def keep(path, device_index=0):
        tr = extract(path, device_index)
        seen.update(trace=tr, scopes=scopes.from_xplane(path, device_index))
        return tr

    trace.extract = keep
    out = run.run_cell(a.workload[0], a.seed, a.seconds, True)
    print(json.dumps(out), flush=True)
    text = trimmed(seen["trace"], a.max_kb * 1024, seen["scopes"], a.at)
    with open(a.out, "w") as f:
        f.write(text)
    part, m = scopes.load(text)
    print(f"map of {len(seen['scopes'])} instruction(s), e.g. "
          f"{next(iter(seen['scopes'].items()), None)}", file=sys.stderr)
    print(f"recorded {len(part.ops)} device op(s), {len(m)} scoped names, "
          f"{len(text)} B, {(part.window[1] - part.window[0]) * 1e-9!r} s "
          f"-> {a.out}", file=sys.stderr)
    return 0


class _Captured(Exception):
    pass


def record_hlo(a) -> int:
    import importlib

    import jax

    import run
    from harness import program, traffic

    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro.launch.bootstrap import enable_compile_cache

    enable_compile_cache()
    for name in a.workload:
        c = run.load_cell(name)
        cfg, cell = c["config"], c["cell"]
        waves, _ = traffic.window_waves(c["traffic"], int(cfg["cases"]), cfg["dt"],
                                        int(cfg["record_steps"]), a.seed)
        drv = importlib.import_module(f"drivers.{cell['driver']}").Driver(
            cfg, cell, program.make_mesh(cfg), waves)
        entry, shapes = drv.fn, []

        def spy(*args):  # the entry's arguments as a call passes them
            shapes.extend(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)), args))
            raise _Captured

        drv.fn = spy
        try:
            drv.call()
        except _Captured:
            pass
        text = strip_kernel_locations(strip_metadata(
            entry.lower(*shapes).compile().as_text()))
        drv.free()
        out = f"{a.out}_{name}.txt"
        with open(out, "w") as f:
            f.write(text)
        sha = lambda t: hashlib.sha256(t.encode()).hexdigest()  # noqa: E731
        print(json.dumps({"workload": name, "hlo_sha256": sha(text),
                          "hlo_sha256_canonical_names": sha(canonical_names(text)),
                          "bytes": len(text), "out": out}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("trace", "hlo"))
    ap.add_argument("--workload", required=True, action="append",
                    help="a cell; hlo takes several")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--max-kb", type=int, default=380)
    ap.add_argument("--at", choices=("boundary", "middle"), default="boundary",
                    help="trace: where in the second call the recording lies")
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    return record_trace(a) if a.what == "trace" else record_hlo(a)


if __name__ == "__main__":
    sys.exit(main())
