"""The part of the collective time per step during which no other
operation runs on that chip, averaged over the chips, in milliseconds."""


def read(ctx):
    _, exposed = ctx["trace"].collective_s()
    return exposed * 1e3 / ctx["calls"]
