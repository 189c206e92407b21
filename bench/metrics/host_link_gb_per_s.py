"""Rate at which the host link moves the spring state, from the
``Async XLA Ops`` line.  There each host↔HBM transfer is one
``copy-start`` event, from its issue to its completion, whose source
(host to HBM) or destination (HBM to host) lives in host memory, memory
space ``S(5)``; in the first TPU v5e traces:

    %copy-start = (f32[12288,150]{0,1:T(8,128)}, f32[12288,150]{0,1:T(8,128)S(5)},
        u32[]{:S(2)}) copy-start(f32[12288,150]{0,1:T(8,128)S(5)} %carry_1__0__0__0_.1)

The link moves one copy of a direction at a time: there some five are
issued together and complete 0.527 ms apart.  So a copy's transfer lies
between the later of its issue and the completion of the copy before it
in its direction, and its own completion; its rate is its bytes over that
time.  A copy that also waited on the program (the next block of the
double buffer) reads slower by that wait, so the rate of the link is the
median over the copies that complete in the window."""
import statistics

from harness import trace
from work import host_link

PATTERN = r"^%[\w.-]+ = .*\scopy-start\(.*"


def transfers(ctx) -> list[tuple[float, float]]:
    """``(bytes, seconds)`` of each host-link copy completing in the window."""
    lo, hi = ctx.trace.window
    copies = [o for o in trace.matching(ctx.trace.async_ops, (PATTERN,))
              if "S(5)" in o[0] and lo <= o[1] + o[2] <= hi]
    out = []
    for to_device in (True, False):
        prev = float("-inf")
        mine = [o for o in copies if host_link.from_host(o[0]) == to_device]
        for name, start, dur in sorted(mine, key=lambda o: o[1] + o[2]):
            end = start + dur
            out.append((host_link.copy_bytes(name), (end - max(start, prev)) * 1e-9))
            prev = end
    return out


def read(ctx):
    rates = [b / t for b, t in transfers(ctx) if t > 0]
    return statistics.median(rates) / 1e9 if rates else None
