"""The readers of the program's own host spans and counters: span time
clipped to the traced window, counter ratios, and nothing where the
program or the path has no such span or counter."""
import sys
from pathlib import Path

import pytest

from bench import run as R
from bench import trace as T

ROOT = Path(__file__).resolve().parents[2]


def reader(name):
    return R.import_file(ROOT / "bench" / "metrics" / f"{name}.py",
                         f"obs_metric_{name.replace('.', '_')}")


def reduced(host):
    """A window [100, 1100) ns around `host`, with one idle chip."""
    dev = T.DeviceTrace(0, [("fusion", 100, 200)], [("jit", 100, 200)])
    return T.Reduced([dev], [("bench.window", 100, 1100)] + host, 100, 1100)


def sparse_call():
    # one call from 50 ns, before the window opens, to 1,150 ns, after
    # it closes: prepare 50-150 (50 ns inside), the slice loop 150-900,
    # stats 1,000-1,150 (100 ns inside)
    return [("bench.call", 50, 1150), ("fluid.prepare", 50, 150),
            ("fluid.run", 150, 900), ("fluid.sparse.loop", 150, 900),
            ("fluid.sparse.dispatch", 200, 800),
            ("fluid.readback", 900, 1000), ("fluid.stats", 1000, 1150)]


def test_host_prep_clipped_to_window():
    ctx = dict(trace=reduced(sparse_call()), work=10)
    # 50 ns of 100 inside the window over 10 slices: 5 ns = 0.005 us
    assert reader("host_prep_us_per_slice.fluid").read(ctx) == \
        pytest.approx(0.005)


def test_host_loop_reads_the_sparse_loop_only():
    ctx = dict(trace=reduced(sparse_call()), work=10)
    assert reader("host_loop_us_per_slice.fluid").read(ctx) == \
        pytest.approx(0.075)


def test_host_loop_is_nothing_without_the_sparse_loop():
    dense = [e for e in sparse_call() if not e[0].startswith("fluid.sparse")]
    ctx = dict(trace=reduced(dense), work=10)
    assert reader("host_loop_us_per_slice.fluid").read(ctx) is None
    assert reader("host_prep_us_per_slice.fluid").read(ctx) is not None


def test_span_readers_are_nothing_for_a_program_without_spans():
    ctx = dict(trace=reduced([("bench.call", 100, 1100)]), work=10)
    for name in ("host_prep_us_per_slice.fluid",
                 "host_loop_us_per_slice.fluid",
                 "host_chunk_us_per_step.flow"):
        assert reader(name).read(ctx) is None


def test_host_chunk_sums_fill_and_retire():
    host = [("bench.call", 0, 1200), ("flows.run", 0, 1200)]
    for c0 in (0, 400, 800):            # three chunks of 400 ns
        host += [("flows.tiled.fill", c0, c0 + 30),
                 ("flows.tiled.upload", c0 + 30, c0 + 50),
                 ("flows.tiled.chunk", c0 + 50, c0 + 60),
                 ("flows.tiled.readback", c0 + 60, c0 + 380),
                 ("flows.tiled.retire", c0 + 380, c0 + 400)]
    ctx = dict(trace=reduced(host), work=20)
    # in the window [100, 1100): fills 400-430 and 800-830, retires
    # 380-400 and 780-800 (the fill at 0 and the retire at 1,180 lie
    # outside): 100 ns over 20 steps = 0.005 us
    assert reader("host_chunk_us_per_step.flow").read(ctx) == \
        pytest.approx(0.005)


@pytest.fixture()
def obs():
    from repro import obs

    obs.reset()
    yield obs
    obs.reset()


@pytest.mark.parametrize("name,units,nbytes", [
    ("h2d_bytes_per_slice.fluid", "fluid.scenario_slices", "fluid.h2d_bytes"),
    ("h2d_bytes_per_step.flow", "flows.scenario_steps", "flows.h2d_bytes"),
])
def test_counter_readers(obs, name, units, nbytes):
    ctx = dict(trace=None, work=1)
    read = reader(name).read
    assert read(ctx) is None                   # nothing counted yet
    obs.count(units, 207_360)
    obs.count(nbytes, 7_278_336)
    obs.count(units, 207_360)                  # a second call
    obs.count(nbytes, 7_278_336)
    assert read(ctx) == pytest.approx(7_278_336 / 207_360)


@pytest.mark.parametrize("name", ["h2d_bytes_per_slice.fluid",
                                  "h2d_bytes_per_step.flow"])
def test_counter_readers_without_counters(monkeypatch, name):
    """A program that predates `repro.obs` reads as nothing, not as an
    error."""
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader(name).read(dict(trace=None, work=1)) is None
