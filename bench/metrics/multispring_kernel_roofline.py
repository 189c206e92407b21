"""Share of the multispring kernel's roofline: every case-step of the
window updates every spring once (``work/multispring_update``); the least
time of those updates over the kernel's measured device time."""
from harness import peaks
from metrics import multispring_kernel_ms_per_step as k

from work import multispring_update


def read(ctx):
    ns = k.kernel_ns(ctx)
    if ns <= 0:
        return None
    fl, by = multispring_update.count(ctx.config["n_elem"], ctx.config["nspring"])
    n = ctx.case_steps
    share, bound = peaks.roofline_share(n * fl, n * by, ns * 1e-9, ctx.device_kind)
    ctx.notes["multispring_kernel_roofline"] = f"{bound} bound, {n} updates"
    return share
