"""Device time of the Pallas rotor slice kernel (`rotor_slice_step`) per
requested scenario-slice, in microseconds; nothing where it did not run."""

KERNEL = "rotor_slice_step"


def read(ctx):
    s = ctx["trace"].op_seconds().get(KERNEL)
    return None if s is None else s * 1e6 / ctx["work"]
