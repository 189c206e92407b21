"""Device time of the Pallas multispring kernel (``_ms_kernel``) per
case-step: the summed durations of its events in the traced window.  Its
custom call is named after ``multispring_pallas`` (first TPU v5e trace)."""
from harness import trace

EVENTS = (trace.kernel("multispring_pallas"),)


def kernel_ns(ctx):
    ops, _ = trace.windowed(ctx.trace)
    return sum(d for _, _, d in trace.matching(ops, EVENTS))


def read(ctx):
    ns = kernel_ns(ctx)
    return ns * 1e-6 / ctx.case_steps if ns > 0 else None
