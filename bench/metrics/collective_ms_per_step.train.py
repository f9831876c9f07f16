"""Time of the collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, send, recv) per step,
averaged over the chips, in milliseconds."""


def read(ctx):
    total, _ = ctx["trace"].collective_s()
    return total * 1e3 / ctx["calls"]
