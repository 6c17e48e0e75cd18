"""Device seconds per iteration of cross-chip collectives (all-reduce,
all-gather, collective-permute, ...), mean over devices.  Nothing to read
on one chip."""


def read(ctx):
    if ctx.n_devices < 2:
        return None
    return ctx.per_iteration_s("collective_ns")
