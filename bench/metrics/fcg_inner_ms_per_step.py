"""Device time per case-step under the ``repro.fcg_inner`` scope: the fixed
fp32 inner PCG sweeps that precondition each outer FCG iteration
(``solver.make_inner_pcg_preconditioner``), with the EBE products, gathers
and scatters they run."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.fcg_inner")
