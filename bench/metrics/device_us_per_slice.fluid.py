"""Device busy time per requested scenario-slice, in microseconds."""


def read(ctx):
    return ctx["trace"].busy_s() * 1e6 / ctx["work"]
