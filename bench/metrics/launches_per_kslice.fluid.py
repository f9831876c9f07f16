"""Device program executions per 1000 requested scenario-slices: how
often the host goes back to the chip (host driver layer)."""


def read(ctx):
    return ctx["trace"].launches() / (ctx["work"] / 1e3)
