"""Which layer of the program each device operation ran in, and the device
time of each layer.

The program opens a ``jax.named_scope`` named ``repro.<layer>`` around each
layer of the FEM step (PERF.md section 3 lists them).  JAX writes the scope
path into every HLO instruction's ``op_name`` metadata, through ``jit``,
``vmap``, ``while_loop``, ``fori_loop`` and ``scan``, e.g.
``jit(chunk)/while/body/vmap()/repro.fcg_outer/while/body/repro.fcg_inner/
while/body/repro.ebe_gather/gather``.  A fusion carries its fused root's
metadata, so a fusion is attributed to the layer of its root.

:func:`from_xplane` reads the instruction → ``op_name`` map from a
profiler ``.xplane.pb``; the rules below reduce a :class:`harness.trace.
Trace` with that map:

* an operation belongs to its innermost ``repro.`` scope (:func:`innermost`);
  one that carries none is unscoped;
* the device time under a scope S is the union, inside the window, of the
  intervals of every operation with S anywhere on its path
  (:func:`ns_under`), so a scope's time holds the scopes nested in it.

The map is keyed by instruction name (``fusion.391``): one program runs in
a benchmark window, and an instruction's name is unique in its module.
"""
from __future__ import annotations

import glob
import json
import os
import re
import tempfile

from harness import trace

_SCOPE = re.compile(r"repro\.[A-Za-z0-9_.]+")


def instr(name: str) -> str:
    """``%fusion.391 = f32[...] fusion(...)`` → ``fusion.391``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def chain(op_name: str) -> list[str]:
    """The ``repro.`` scopes on an ``op_name`` path, outermost first.  A
    scope that a transformation wraps (``vmap(repro.x)``) still counts; of
    the paths XLA joins with ``;`` when it merges operations, the first."""
    out = []
    for seg in op_name.split(";", 1)[0].split("/"):
        m = _SCOPE.search(seg)
        if m:
            out.append(m.group(0))
    return out


def innermost(op_name: str) -> str | None:
    c = chain(op_name)
    return c[-1] if c else None


# ---------------------------------------------------------------------------
# the map, from a profiler trace
# ---------------------------------------------------------------------------
#
# ``jax.profiler.ProfileData`` gives each event's name and timing but not the
# metadata of the event kinds, which is where the device's op names live, so
# the file is read here with a protobuf wire-format reader (XSpace, xplane.
# proto of the TSL profiler; HloProto of XLA).  Field numbers:
#   XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map id → XEventMetadata),
#   .stat_metadata 5 (map id → XStatMetadata); map entry key 1, value 2;
#   XEventMetadata.name 2, .display_name 4, .stats 5; XStatMetadata.id 1,
#   .name 2; XStat.metadata_id 1, .str_value 5, .bytes_value 6, .ref_value 7;
#   HloProto.hlo_module 1; HloModuleProto.computations 3;
#   HloComputationProto.instructions 2; HloInstructionProto.name 1,
#   .metadata 7; OpMetadata.op_name 2.

OP_NAME_STAT = "tf_op"       # a TPU op kind's op_name (first TPU v5e traces)
HLO_PROTO_STAT = "Hlo Proto"  # a program's module, on the metadata plane


def _varint(b, i):
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if c < 0x80:
            return x, i
        s += 7


def fields(b):
    """``(field, value)`` of one message; a length-delimited value is a
    ``memoryview`` slice, a fixed one its raw bytes, a varint an int."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield f, v


def _first(msg, field, default=b""):
    for f, v in fields(msg):
        if f == field:
            return v
    return default


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane(msg):
    """``(name, {event-metadata id: (name, display_name, [(stat, value)])})``
    of one XPlane, its lines skipped."""
    name, stat_names, raw = "", {}, []
    for f, v in fields(msg):
        if f == 2:
            name = _text(v)
        elif f == 4:
            raw.append(_first(v, 2))
        elif f == 5:
            sm = _first(v, 2)
            stat_names[_first(sm, 1, 0)] = _text(_first(sm, 2))
    events = {}
    for em in raw:
        eid, nm, disp, stats = 0, "", "", []
        for f, v in fields(em):
            if f == 1:
                eid = v
            elif f == 2:
                nm = _text(v)
            elif f == 4:
                disp = _text(v)
            elif f == 5:
                sid, val = 0, None
                for g, w in fields(v):
                    if g == 1:
                        sid = w
                    elif g in (5, 6):
                        val = w
                    elif g == 7:
                        val = ("ref", w)
                stats.append((sid, val))
        resolved = []
        for sid, val in stats:
            if isinstance(val, tuple):  # a reference to an interned string
                val = stat_names.get(val[1], "").encode()
            resolved.append((stat_names.get(sid, ""), val))
        events[eid] = (nm, disp, resolved)
    return name, events


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name → ``op_name`` of every instruction of an HloProto
    that carries one."""
    out = {}
    for comp in (v for f, v in fields(_first(hlo_proto, 1)) if f == 3):
        for ins in (v for f, v in fields(comp) if f == 2):
            nm, op = "", ""
            for f, v in fields(ins):
                if f == 1:
                    nm = _text(v)
                elif f == 7:
                    op = _text(_first(v, 2))
            if op:
                out[nm] = op
    return out


def from_xplane(path: str, device_index: int = 0) -> dict[str, str]:
    """Instruction name → ``op_name`` of the device's operations: from the
    op-name stat of the device's event kinds where it has one, else from
    the programs' modules that the trace stores on its metadata plane."""
    with open(path, "rb") as f:
        data = f.read()
    want = f"/device:TPU:{device_index}"
    on_device, from_modules, modules = {}, {}, []
    for fno, pmsg in fields(data):
        if fno != 1:
            continue
        name = _text(_first(pmsg, 2))
        if name == want or name.startswith(want + " "):
            for nm, disp, stats in _plane(pmsg)[1].values():
                op = next((_text(v) for s, v in stats
                           if s == OP_NAME_STAT and v is not None), "")
                if op:
                    for n in (nm, disp):
                        if n:
                            on_device[instr(n)] = op
        elif name == "/host:metadata":
            modules += [hlo_op_names(v) for _, _, stats in _plane(pmsg)[1].values()
                        for s, v in stats if s == HLO_PROTO_STAT and v is not None]
    # where two programs name an instruction alike, the larger (the entry
    # that the window runs) wins
    for m in sorted(modules, key=len):
        from_modules.update(m)
    return on_device or from_modules


def span_times(path: str, name: str) -> list[tuple[float, float]]:
    """``(start_ns, dur_ns)`` of each host event called ``name`` in a
    profile, on the clock ``jax.profiler.ProfileData`` gives it (a line's
    ``timestamp_ns`` plus the event's ``offset_ps``).  Field numbers:
    XPlane.lines 3; XLine.timestamp_ns 3, .events 4; XEvent.metadata_id 1,
    .offset_ps 2, .duration_ps 3."""
    with open(path, "rb") as f:
        data = f.read()
    out = []
    for fno, pmsg in fields(data):
        if fno != 1 or not _text(_first(pmsg, 2)).startswith("/host:"):
            continue
        ids, lines = set(), []
        for f, v in fields(pmsg):
            if f == 4 and _text(_first(_first(v, 2), 2)) == name:
                ids.add(_first(_first(v, 2), 1, 0))
            elif f == 3:
                lines.append(v)
        for line in lines if ids else []:
            ts = _first(line, 3, 0)
            for f, ev in fields(line):
                e = dict(fields(ev)) if f == 4 else {}
                if e.get(1) in ids:
                    out.append((ts + e.get(2, 0) / 1000, e.get(3, 0) / 1000))
    return out


def _run_profile(ctx) -> str | None:
    """The profile ``ctx.trace`` was read from: of the ``.xplane.pb`` files
    under a ``bench_trace_*`` directory of the temporary directory (where
    ``bench/run.py`` traces the window, and which it removes only after
    every reader has run), the newest whose ``bench.window`` span is the
    trace's own to the nanosecond.  Another run's profile, one older or one
    written meanwhile, never matches; None where none does."""
    lo, hi = ctx.trace.window
    pat = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**", "*.xplane.pb")
    for path in sorted(glob.glob(pat, recursive=True), key=os.path.getmtime, reverse=True):
        if any(abs(s - lo) <= 1 and abs(s + d - hi) <= 1
               for s, d in span_times(path, trace.WINDOW_SPAN)):
            return path
    return None


def of(ctx) -> dict[str, str]:
    """The run's instruction → ``op_name`` map: ``ctx.scopes`` where a
    recorded trace brings one, else read once from the run's own profile;
    empty where that is not found, so the readers read nothing."""
    if getattr(ctx, "scopes", None) is None:
        path = _run_profile(ctx)
        ctx.scopes = from_xplane(path) if path else {}
    return ctx.scopes


def scoped_ops(ctx) -> list:
    """``[chain, start_ns, dur_ns]`` of each device operation in the window,
    ``chain`` its ``repro.`` scopes (a tuple, outermost first); computed
    once per run."""
    if getattr(ctx, "scoped_ops", None) is None:
        m, chains = of(ctx), {}
        ops, _ = trace.windowed(ctx.trace)
        out = []
        for name, s, d in ops:
            k = instr(name)
            if k not in chains:
                chains[k] = tuple(chain(m.get(k, "")))
            out.append([chains[k], s, d])
        ctx.scoped_ops = out
    return ctx.scoped_ops


def has_scopes(ctx) -> bool:
    """Whether any operation of the window carries a ``repro.`` scope; a
    program without scopes (one older than them) reads nothing."""
    return any(c for c, _, _ in scoped_ops(ctx))


# ---------------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------------


def ns_under(ctx, scope: str) -> float:
    """Device time (ns) in the window of the operations under ``scope``:
    the union of their intervals."""
    return trace.covered(o for o in scoped_ops(ctx) if scope in o[0])


def ms_per_step(ctx, scope: str) -> float | None:
    """Device ms per case-step under ``scope``; None where nothing ran
    under it (or the program has no scopes)."""
    ns = ns_under(ctx, scope) if has_scopes(ctx) else 0.0
    return ns * 1e-6 / ctx.case_steps if ns > 0 else None


def table(ctx) -> dict[str, float]:
    """Device ms per case-step by innermost scope (``""``: unscoped), each
    the union of its operations' intervals in the window, largest first."""
    groups: dict[str, list] = {}
    for o in scoped_ops(ctx):
        groups.setdefault(o[0][-1] if o[0] else "", []).append(o)
    ms = {k: trace.covered(v) * 1e-6 / ctx.case_steps for k, v in groups.items()}
    return dict(sorted(ms.items(), key=lambda kv: -kv[1]))


# ---------------------------------------------------------------------------
# recorded traces
# ---------------------------------------------------------------------------


def record(tr: trace.Trace, scopes: dict[str, str]) -> str:
    """A recorded trace with its scope map, as JSON: the map keeps only the
    instructions the trace holds."""
    names = {instr(o[0]) for o in tr.ops}
    keep = {k: v for k, v in scopes.items() if k in names}
    return json.dumps({"trace": json.loads(tr.to_json()), "scopes": keep})


def load(text: str) -> tuple[trace.Trace, dict[str, str]]:
    d = json.loads(text)
    return trace.Trace(**d["trace"]), d["scopes"]
