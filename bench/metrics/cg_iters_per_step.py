"""Outer CG iterations (FCG for EBE, PCG for CRS) per case-step in the
window, as each step returns them (``StepAux.iters``)."""


def read(ctx):
    its = ctx.iters
    return float(sum(its)) / len(its) if its else None
