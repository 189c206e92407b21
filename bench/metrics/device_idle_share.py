"""Share of the traced window in which no operation ran on the device:
1 − (union of device op intervals) / window."""
from harness import trace


def read(ctx):
    busy, win = trace.busy_idle(ctx.trace)
    return 100.0 * (1.0 - busy / win) if busy > 0 else None
