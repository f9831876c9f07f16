"""Host time in the sparse engine's slice loop (`fluid.sparse.loop`) per
requested scenario-slice, in microseconds; nothing where the loop did
not run (the dense engine) or the program has no such span."""
from bench.host_spans import span_seconds


def read(ctx):
    s = span_seconds(ctx["trace"], ["fluid.sparse.loop"])
    return None if s is None else s * 1e6 / ctx["work"]
