"""Device time per case-step under the ``repro.bcsr_spmv`` scope: the
stored-matrix SpMV (``spmv.bcsr_matvec``) of every PCG iteration and of the
damping product, its gather (``repro.bcsr_gather``) and sorted scatter-add
(``repro.bcsr_scatter``) included."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.bcsr_spmv")
