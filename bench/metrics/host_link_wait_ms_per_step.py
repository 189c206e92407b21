"""Device time per step spent waiting on the host link: the union of the
``copy-start`` and ``copy-done`` operations whose source or destination
lives in host memory (memory space ``S(5)``) on the ``XLA Ops`` line, in
the traced window.  A ``copy-done`` there lasts while the core waits for
its transfer, so this is the part of the link's time
(``host_link_ms_per_step``) that the step does not hide behind compute."""
from harness import trace

EVENTS = (r"^%[\w.-]+ = .*\s(copy-start|copy-done)\(.*S\(5\)",)


def read(ctx):
    ops, _ = trace.windowed(ctx.trace)
    ns = trace.covered(trace.matching(ops, EVENTS))
    return ns * 1e-6 / ctx.steps if ns > 0 else None
