"""Percent of the traced window in which no op ran, on the idlest device:
1 - (union of op intervals) / window."""


def read(ctx):
    devs = ctx.red.devices.values()
    w = ctx.red.window_ns
    if not devs or w <= 0:
        return None
    return max(100.0 * (1.0 - d.busy_ns / w) for d in devs)
