"""Share of the roofline reached by the voxel-driven backprojection kernel (%)."""


def read(ctx):
    return ctx.roofline_share("bp_voxel")
