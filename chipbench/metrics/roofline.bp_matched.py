"""Share of the roofline reached by the matched backprojection kernel (%)."""


def read(ctx):
    return ctx.roofline_share("bp_matched")
