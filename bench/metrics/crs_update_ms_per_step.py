"""Device time per case-step under the ``repro.crs_update`` scope: the
step's BCSR assembly of A and of the damping matrix and A's block-Jacobi
inverse (``FemOperators.crs_update``, the paper's UpdateCRS)."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "repro.crs_update")
