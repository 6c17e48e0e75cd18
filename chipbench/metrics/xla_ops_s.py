"""Device seconds per iteration of every op that is neither a kernel of the
kernel table nor a cross-chip collective: layout glue around the kernels
(transposes, pads, the -90 deg scene rotation, the dominance merge) and
the algorithm's vector updates and sums.  Mean over devices."""


def read(ctx):
    return ctx.per_iteration_s("other_ns")
