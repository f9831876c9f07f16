"""Device program executions per 1000 requested scenario-steps: the
tiled engine's chunk loop and its uploads (host driver layer)."""


def read(ctx):
    return ctx["trace"].launches() / (ctx["work"] / 1e3)
