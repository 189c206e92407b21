"""Share of the EBE kernel's roofline: the least time of the products it
ran (``work/ebe_product``), over its measured device time.  Each event is
one call of the kernel, which the k-set vmap batches over every case of
the cell, so it holds ``cases`` products."""
from harness import peaks, trace
from metrics import ebe_kernel_ms_per_step as k

from work import ebe_product


def read(ctx):
    ops, _ = trace.windowed(ctx.trace)
    evs = trace.matching(ops, k.EVENTS)
    ns = sum(d for _, _, d in evs)
    if ns <= 0:
        return None
    fl, by = ebe_product.count(ctx.config["n_elem"], ctx.config["n_nodes"])
    n = len(evs) * ctx.cases
    share, bound = peaks.roofline_share(n * fl, n * by, ns * 1e-9, ctx.device_kind)
    ctx.notes["ebe_kernel_roofline"] = f"{bound} bound, {n} products"
    return share
