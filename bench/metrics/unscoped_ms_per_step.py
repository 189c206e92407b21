"""Device busy time per case-step of the operations that carry no
``repro.`` scope: the union of their intervals in the window.  Work the
program adds without naming its layer shows here.

The reader also leaves the step's split by innermost scope (ms per
case-step, ``harness.scopes.table``) in the run's notes."""
from harness import scopes


def read(ctx):
    if not scopes.has_scopes(ctx):
        return None
    split = scopes.table(ctx)
    ctx.notes["scopes_ms_per_step"] = {k or "(unscoped)": v for k, v in split.items()}
    return split.get("", 0.0)
