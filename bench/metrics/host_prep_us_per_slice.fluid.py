"""Host time in the fluid engine's `fluid.prepare` span (demand
normalisation, schedule export, uploads) per requested scenario-slice,
in microseconds; nothing where the program has no such span."""
from bench.host_spans import span_seconds


def read(ctx):
    s = span_seconds(ctx["trace"], ["fluid.prepare"])
    return None if s is None else s * 1e6 / ctx["work"]
