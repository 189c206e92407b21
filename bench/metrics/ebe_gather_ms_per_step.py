"""Device time per case-step under the ``repro.ebe_gather`` scope: the
nodal gathers into ``[10, 3, E]`` of every EBE product
(``spmv.gather_nodal``), outer and inner solver, damping and diagonal."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.ebe_gather")
