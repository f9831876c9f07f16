"""Bytes the flow engine's host code puts on the device from host data, per
requested scenario-step: the program's counters `flows.h2d_bytes` over
`flows.scenario_steps`, summed over the same calls (set-up and window);
nothing where the program has no counters."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    steps = c.get("flows.scenario_steps")
    return c.get("flows.h2d_bytes", 0) / steps if steps else None
