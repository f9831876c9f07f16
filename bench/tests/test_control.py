"""The control — the plain reference one precision step below the
configuration's, put in the program's place — fails the real cells'
limits at a size a test run holds.  On the chip it was read at the
cells' own sizes (PERF.md, Findings)."""
import jax
import pytest

from bench import run as R

SHRINK = {
    # the cell's configuration and traffic, with fewer scenarios, cycles
    # or seconds of arrivals
    "fluid-648-bulk": dict(seeds_per_call=2, max_cycles=3, batches=1),
    "flows-648-websearch": dict(horizon_s=0.1, tail_s=0.025, batches=1),
}


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
@pytest.mark.parametrize("cell", sorted(SHRINK))
def test_control_is_not_correct(cell, seed):
    res = R.resolve(cell)
    driver = R.import_file(res["driver"], f"control_{cell}")
    c = driver.Cell(res["config"], dict(res["traffic"], **SHRINK[cell]),
                    seed, jax.devices())
    want = c.reference(0)
    sound = c.compare(0, c.call(0), want)
    control = c.compare(0, c.control(0), want)
    limits = res["limits"]
    assert all(v <= limits[k] for k, v in sound.items()), sound
    assert any(v > limits[k] for k, v in control.items()), control
