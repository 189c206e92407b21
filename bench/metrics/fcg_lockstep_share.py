"""Share of the device's outer FCG iterations spent on members of the k-set
that had already converged.  Under the k-set ``vmap`` the batched
``while_loop`` runs until its slowest member converges: a call of ``B``
members whose outer iterations are ``it_b`` (``StepAux.iters``, which each
call returns per member) runs ``B · max_b it_b`` member-iterations, of
which ``Σ_b it_b`` do work.  Over the window's calls:

    Σ_t (B · max_b it − Σ_b it) / Σ_t (B · max_b it) × 100

It reads 0 where B = 1."""


def read(ctx):
    its, B = ctx.iters, int(ctx.cases)
    calls = [its[i:i + B] for i in range(0, len(its) - len(its) % B, B)]
    run = sum(B * max(c) for c in calls)
    if run <= 0:
        return None
    return 100.0 * (run - sum(sum(c) for c in calls)) / run
