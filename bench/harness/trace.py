"""From a profiler trace to device intervals: the reduction every per-layer
reader shares.

``extract`` reads one ``.xplane.pb`` (``jax.profiler.ProfileData``) into a
:class:`Trace`: the device operations of one chip (its ``XLA Ops`` line),
its asynchronous operations (the ``Async XLA Ops`` line: a ``copy-start``
there lasts from its issue to its ``copy-done``), and the host spans the
harness wrote with ``TraceAnnotation`` (names starting ``bench.``).  Everything else here is plain interval arithmetic on
that, so a recorded trace (``Trace.to_json``) can be reduced on any host.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Iterable

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# An event's name is the HLO instruction's text, ``%name = <shape> op(...)``.
# A while loop, conditional or call is one event that encloses the events
# of its body: it is left out, so that no time is counted twice.
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
CONTAINERS = frozenset({"while", "conditional", "call"})


def opcode(name: str) -> str:
    m = _OPCODE.search(name)
    return m.group(1) if m else ""


def kernel(fn_name: str) -> str:
    """Pattern of the events of a Pallas kernel: its custom call is named
    after the jitted wrapper (``vmap_jit_<fn>__`` under ``vmap``)."""
    return rf"^%[\w.]*{fn_name}[\w.]* = "


@dataclasses.dataclass
class Trace:
    ops: list          # [name, start_ns, dur_ns] device operations, no containers
    spans: list        # [name, start_ns, dur_ns] host spans of the harness
    device: str = ""
    lines: dict = dataclasses.field(default_factory=dict)  # line → event count
    async_ops: list = dataclasses.field(default_factory=list)  # [name, start_ns, dur_ns]

    @property
    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0][1], w[0][1] + w[0][2]

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(text: str) -> "Trace":
        return Trace(**json.loads(text))


def extract(path: str, device_index: int = 0) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops, async_ops, spans, lines, dev = [], [], [], {}, ""
    want = f"/device:TPU:{device_index}"
    for plane in pd.planes:
        if plane.name == want or plane.name.startswith(want + " "):
            dev = plane.name
            for line in plane.lines:
                evs = list(line.events)
                lines[line.name] = len(evs)
                if line.name == OPS_LINE:
                    ops += [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in evs if opcode(e.name) not in CONTAINERS]
                elif line.name == ASYNC_LINE:
                    async_ops += [[e.name, float(e.start_ns), float(e.duration_ns)]
                                  for e in evs]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, float(e.start_ns), float(e.duration_ns)])
    if not dev:
        raise ValueError(f"trace has no plane {want}")
    return Trace(ops=ops, spans=spans, device=dev, lines=lines, async_ops=async_ops)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def clip(ops: Iterable, lo: float, hi: float) -> list:
    out = []
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def union(ops: Iterable) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals covered by any of ``ops``."""
    iv = sorted((s, s + d) for _, s, d in ops if d > 0)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(ops: Iterable) -> float:
    return sum(b - a for a, b in union(ops))


def matching(ops: Iterable, patterns: Iterable[str]) -> list:
    rx = re.compile("|".join(patterns))
    return [o for o in ops if rx.search(o[0])]


def not_matching(ops: Iterable, patterns: Iterable[str]) -> list:
    rx = re.compile("|".join(patterns))
    return [o for o in ops if not rx.search(o[0])]


def windowed(tr: Trace) -> tuple[list, float]:
    """Device ops clipped to the window, and the window's length (ns)."""
    lo, hi = tr.window
    return clip(tr.ops, lo, hi), hi - lo


def busy_idle(tr: Trace) -> tuple[float, float]:
    """(busy seconds, window seconds): busy is the union of op intervals."""
    ops, win = windowed(tr)
    return covered(ops) * 1e-9, win * 1e-9


def gaps(tr: Trace) -> list[tuple[float, float]]:
    """Idle intervals of the device inside the window."""
    lo, hi = tr.window
    ops, _ = windowed(tr)
    out, t = [], lo
    for a, b in union(ops):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(tr: Trace, t: float) -> str:
    """The innermost harness span (other than the window) covering ``t``."""
    best, best_d = "host outside any harness span", float("inf")
    for name, s, d in tr.spans:
        if name != WINDOW_SPAN and s <= t <= s + d and d < best_d:
            best, best_d = name, d
    return best


def short(name: str) -> str:
    """``%fusion.391 = f32[...] fusion(...)`` → ``fusion.391 fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    op = opcode(name)
    return f"{head} {op}" if op else head


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by what the host was doing, each as ``[name, seconds]``."""
    ops, _ = windowed(tr)
    by: dict[str, float] = {}
    for name, _, d in ops:
        by[short(name)] = by.get(short(name), 0.0) + d
    dev = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    gs = sorted(gaps(tr), key=lambda g: -(g[1] - g[0]))[:top]
    return {"device_ops": [[n, d * 1e-9] for n, d in dev],
            "idle_gaps": [[host_activity(tr, 0.5 * (a + b)), (b - a) * 1e-9]
                          for a, b in gs]}
