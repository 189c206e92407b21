"""Gigabytes of spring state that cross the host link per step, both ways:
twice the bytes of the carry's ``pinned_host`` leaves."""


def read(ctx):
    b = ctx.host_link_bytes
    return b / 1e9 if b > 0 else None
