"""repro.obs: the engines' host spans and counters.

Each engine path runs once plain and once under the JAX profiler: the
spans it passes through must appear on one host line of the trace, each
inside its parent, and the profiled results must equal the plain ones
bit for bit.  The counters must count what the engines upload.
"""
import dataclasses
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.topology import build_opera_topology
from repro.netsim.faults import (
    FailureEvent,
    FailureSchedule,
    apply_flow_faults,
)
from repro.netsim.flows import build_scenario
from repro.netsim.flows_jax import simulate_flows_batch
from repro.netsim.fluid_jax import simulate_rotor_bulk_batch
from repro.netsim.sweep import DesignPoint, scenario_demand

# each span's enclosing span (None: opened directly by the caller)
PARENT = {
    "fluid.prepare": None, "fluid.run": None, "fluid.readback": None,
    "fluid.stats": None,
    "fluid.sparse.loop": "fluid.run",
    "flows.prepare": None, "flows.run": None, "flows.readback": None,
    "flows.finalize": None,
    "flows.tiled.fill": "flows.run", "flows.tiled.upload": "flows.run",
    "flows.tiled.chunk": "flows.run", "flows.tiled.readback": "flows.run",
    "flows.tiled.retire": "flows.run",
}
FLUID_TOP = {"fluid.prepare", "fluid.run", "fluid.readback", "fluid.stats"}
SPARSE = {"fluid.sparse.loop"}
FLOWS_TOP = {"flows.prepare", "flows.run", "flows.readback",
             "flows.finalize"}
TILED = {"flows.tiled.fill", "flows.tiled.upload", "flows.tiled.chunk",
         "flows.tiled.readback", "flows.tiled.retire"}
CYCLES = 2
FLOW_KW = dict(num_hosts=16, horizon_s=0.03, dt_s=5e-4, tail_s=0.02)


@dataclasses.dataclass
class Fluid:
    cfg: object
    topo: object
    demands: np.ndarray
    faults: object = None

    def run(self, engine):
        return simulate_rotor_bulk_batch(
            self.cfg, self.demands, vlb=True, max_cycles=CYCLES,
            topo=self.topo, faults=self.faults, engine=engine)


def fluid_case(faulted: bool = False) -> Fluid:
    cfg = DesignPoint(k=8, num_racks=16, groups=1).to_config()
    topo = build_opera_topology(cfg.num_racks, cfg.u, seed=0,
                                groups=cfg.groups)
    demands = np.stack([scenario_demand("skew", cfg, 2.5, s)
                        for s in range(2)])
    faults = None
    if faulted:
        faults = FailureSchedule(
            num_racks=cfg.num_racks, num_switches=cfg.u,
            events=(FailureEvent("link", ((1, 0),), onset_step=1,
                                 detect_lag=2, recover_step=9),))
    return Fluid(cfg, topo, demands, faults)


def flow_scenarios(faulted: bool = False):
    scns = [build_scenario("opera", "websearch", 0.1, seed=0, **FLOW_KW),
            build_scenario("opera", "datamining", 0.3, seed=1, **FLOW_KW)]
    if faulted:
        sched = FailureSchedule(
            num_racks=8, num_switches=2, seed=5,
            events=(FailureEvent("tor", (1,), onset_step=10, detect_lag=5,
                                 recover_step=60),))
        scns[0] = apply_flow_faults(scns[0], sched)
    return scns


TILED_KW = dict(engine="tiled", tile_size=64, window_tiles=2,
                chunk_steps=24)

# path -> (how to run it, the spans it passes through)
PATHS = {
    "fluid-dense": (lambda: fluid_case().run("dense"), FLUID_TOP),
    "fluid-sparse": (lambda: fluid_case().run("sparse"), FLUID_TOP | SPARSE),
    "fluid-dense-faulted": (lambda: fluid_case(True).run("dense"),
                            FLUID_TOP),
    "fluid-sparse-faulted": (lambda: fluid_case(True).run("sparse"),
                             FLUID_TOP | SPARSE),
    "flows-dense": (lambda: simulate_flows_batch(flow_scenarios(),
                                                 engine="dense"), FLOWS_TOP),
    "flows-tiled": (lambda: simulate_flows_batch(flow_scenarios(),
                                                 **TILED_KW),
                    FLOWS_TOP | TILED),
    "flows-tiled-faulted": (lambda: simulate_flows_batch(
        flow_scenarios(True), **TILED_KW), FLOWS_TOP | TILED),
}


def host_lines(trace_dir: Path):
    """{"<index>:<line name>": [(name, start, end)]} of the host lines
    that hold any declared span."""
    from jax.profiler import ProfileData

    path = next(trace_dir.rglob("*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name in obs.SPANS]
            if evs:
                lines[f"{i}:{line.name}"] = evs
    return lines


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """path -> (plain result, profiled result, host lines, counters of
    the profiled call)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = {}
    for name, (run, _) in PATHS.items():
        plain = run()                 # compiles outside the trace
        d = tmp_path_factory.mktemp(name)
        obs.reset()
        with jax.profiler.trace(str(d), profiler_options=opts):
            got = run()
        out[name] = (plain, got, host_lines(d), obs.counters())
    return out


@pytest.mark.parametrize("path", sorted(PATHS))
def test_spans_nest_on_one_host_line(traced, path):
    _, _, lines, _ = traced[path]
    assert len(lines) == 1, f"spans on {len(lines)} host lines: {list(lines)}"
    (events,) = lines.values()
    names = {n for n, _, _ in events}
    assert names == PATHS[path][1]
    for n, s, e in events:
        parent = PARENT[n]
        if parent is None:
            continue
        assert any(pn == parent and ps <= s and e <= pe
                   for pn, ps, pe in events), f"{n} outside {parent}"
    # one span per phase and chunk, none per slice or step
    assert sum(n == "fluid.sparse.loop" for n, _, _ in events) <= 1


@pytest.mark.parametrize("path", sorted(PATHS))
def test_profiler_leaves_results_bitwise_equal(traced, path):
    plain, got, _, _ = traced[path]
    np.testing.assert_equal(dataclasses.asdict(got), dataclasses.asdict(plain))


def test_every_declared_name_is_emitted(traced):
    spans = {n for _, _, lines, _ in traced.values()
             for evs in lines.values() for n, _, _ in evs}
    assert spans == set(obs.SPANS)
    assert set(PARENT) == set(obs.SPANS)
    counted = set().union(*(c for _, _, _, c in traced.values()))
    assert counted == set(obs.COUNTERS)


def test_undeclared_names_are_refused():
    with pytest.raises(KeyError):
        obs.span("fluid.nothing")
    with pytest.raises(KeyError):
        obs.count("fluid.nothing", 1)


@pytest.mark.parametrize("engine", ["dense", "sparse"])
def test_fluid_counts_schedule_and_demand(engine):
    case = fluid_case()
    if engine == "sparse":
        sched = np.asarray(case.topo.matching_index_tensor(), np.int32)
    else:
        sched = np.asarray(case.topo.matching_tensor(), np.float32)
    obs.reset()
    case.run(engine)
    c = obs.counters()
    b = case.demands.shape[0]
    assert c["fluid.h2d_bytes"] == sched.nbytes + case.demands.size * 4
    assert c["fluid.scenario_slices"] == b * CYCLES * case.topo.num_slices
    assert "flows.h2d_bytes" not in c


def test_flows_tiled_counts_one_chunk_operands():
    """One chunk over the whole horizon in a window that never grows:
    the five per-scenario constants (4 B each) plus the chunk's window
    operands (f32 rem, rem0, arr_ms, int32 start, class_id, bool
    is_bulk: 21 B a slot)."""
    scns = flow_scenarios()
    steps = scns[0].steps
    tile, window = 64, 64
    obs.reset()
    r = simulate_flows_batch(scns, engine="tiled", tile_size=tile,
                             window_tiles=window, chunk_steps=steps)
    assert 0 < r.peak_window_tiles <= window
    b = len(scns)
    c = obs.counters()
    assert c["flows.h2d_bytes"] == 5 * 4 * b + 21 * b * window * tile
    assert c["flows.scenario_steps"] == b * steps


def test_flows_dense_counts_packed_state():
    """Dense: 25 B a flow slot (f32 remaining, allow_mid, allow_end,
    arr_ms; int32 start, class_id; bool is_bulk) and five 4-byte
    per-scenario constants."""
    scns = flow_scenarios()
    n_max = max(s.num_flows for s in scns)
    obs.reset()
    simulate_flows_batch(scns, engine="dense")
    b = len(scns)
    c = obs.counters()
    assert c["flows.h2d_bytes"] == b * (25 * n_max + 5 * 4)
    assert c["flows.scenario_steps"] == b * scns[0].steps
