"""Bytes the fluid engine's host code puts on the device from host data, per
requested scenario-slice: the program's counters `fluid.h2d_bytes` over
`fluid.scenario_slices`, summed over the same calls (set-up and window);
nothing where the program has no counters."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    slices = c.get("fluid.scenario_slices")
    return c.get("fluid.h2d_bytes", 0) / slices if slices else None
