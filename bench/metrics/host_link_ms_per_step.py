"""Time the host link needs per step: the spring-state bytes that cross it
in a step (``host_link_gb_per_step``) at the rate the trace shows it moving
them (``host_link_gb_per_s``).  How much of it the device waits for is
``host_link_wait_ms_per_step``."""
from metrics import host_link_gb_per_s, host_link_gb_per_step


def read(ctx):
    gb, rate = host_link_gb_per_step.read(ctx), host_link_gb_per_s.read(ctx)
    return 1e3 * gb / rate if gb and rate else None
