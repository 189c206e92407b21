"""Device time per case-step under the ``repro.ebe_scatter`` scope: the
sorted segment-sum scatters of every EBE product (``spmv.scatter_nodal``)."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.ebe_scatter")
