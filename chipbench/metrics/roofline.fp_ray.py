"""Share of the roofline reached by the forward-projection kernel (%)."""


def read(ctx):
    return ctx.roofline_share("fp_ray")
