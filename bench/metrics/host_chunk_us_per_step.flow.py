"""Host time the tiled flow engine spends between its chunk programs,
filling the next window (`flows.tiled.fill`) and retiring drained tiles
(`flows.tiled.retire`), per requested scenario-step, in microseconds;
nothing where the tiled loop did not run or has no such spans."""
from bench.host_spans import span_seconds


def read(ctx):
    s = span_seconds(ctx["trace"], ["flows.tiled.fill", "flows.tiled.retire"])
    return None if s is None else s * 1e6 / ctx["work"]
