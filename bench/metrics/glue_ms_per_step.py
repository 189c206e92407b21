"""Device busy time per case-step outside the two Pallas kernels and the
device's waits on the host link: gathers and scatters, CRS assembly, BCSR
SpMV, relayouts and vector operations (the union of those ops' intervals)."""
from harness import trace
from metrics import ebe_kernel_ms_per_step, host_link_wait_ms_per_step
from metrics import multispring_kernel_ms_per_step

EXCLUDE = (ebe_kernel_ms_per_step.EVENTS + multispring_kernel_ms_per_step.EVENTS
           + host_link_wait_ms_per_step.EVENTS)


def read(ctx):
    ops, _ = trace.windowed(ctx.trace)
    ns = trace.covered(trace.not_matching(ops, EXCLUDE))
    return ns * 1e-6 / ctx.case_steps if ns > 0 else None
