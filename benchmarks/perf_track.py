"""Engine parity gates: the sparse-vs-dense rotor gate and the
tiled-vs-dense flow gate, full engine runs at small points, faulted and
unfaulted.  `scripts/ci_tier1.sh` runs them; the process exits nonzero
on drift.

    PYTHONPATH=src:. python -m benchmarks.perf_track --fast

They time nothing: speed is measured on the chip by `bench/run.py`
(see PERF.md).
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from benchmarks.common import banner, check
from repro.netsim.sweep import DesignPoint


def parity_gate(tol: float = 1e-5) -> bool:
    """Full-engine sparse-vs-dense agreement at the small paper points,
    faulted and unfaulted — the CI drift gate."""
    from repro.core.topology import build_opera_topology
    from repro.netsim.faults import FailureEvent, FailureSchedule
    from repro.netsim.fluid_jax import simulate_rotor_bulk_batch
    from repro.netsim.sweep import scenario_demand

    ok = True
    for dp in (DesignPoint(k=8, num_racks=16, groups=1),
               DesignPoint(k=8, num_racks=16, groups=2)):
        cfg = dp.to_config()
        topo = build_opera_topology(
            cfg.num_racks, cfg.u, seed=0, groups=cfg.groups)
        # overloaded skew: the run must NOT complete, so residual / wire
        # trajectories exercise the VLB spread math, not just the totals
        demands = np.stack([
            scenario_demand("skew", cfg, 2.5, s) for s in range(2)])
        faults = FailureSchedule(
            num_racks=cfg.num_racks, num_switches=cfg.u,
            events=(FailureEvent("link", ((1, 0),), onset_step=1,
                                 detect_lag=2, recover_step=9),
                    FailureEvent("tor", (3,), onset_step=2,
                                 detect_lag=1, recover_step=11)))
        for fs in (None, faults):
            res = {}
            for engine in ("dense", "sparse"):
                res[engine] = simulate_rotor_bulk_batch(
                    cfg, demands, vlb=True, max_cycles=8, topo=topo,
                    faults=fs, engine=engine)
            for field in ("goodput_bytes", "wire_bytes", "residual_bytes"):
                a = getattr(res["dense"], field)
                b = getattr(res["sparse"], field)
                drift = float(np.max(
                    np.abs(a - b) / np.maximum(np.abs(a), 1.0)))
                ok &= check(
                    f"{dp.name} {'faulted' if fs else 'clean'} {field} "
                    f"drift < {tol}", drift < tol, f"{drift:.2e}")
    return ok


def flow_parity_gate() -> bool:
    """Tiled-vs-dense flow-engine agreement — full runs on small grids,
    clean and faulted, with deliberately tiny tiles so the windowing
    and capacity-growth machinery is exercised.  Histograms must match
    bitwise (the engines share the binning math); deficit snapshots to
    f32 reduction-order tolerance; streamed percentiles within one
    histogram bin of the dense engine's exact ones."""
    from repro.netsim.faults import FailureEvent, FailureSchedule, apply_flow_faults
    from repro.netsim.flows import FCT_BIN_LOG2_WIDTH, build_scenario
    from repro.netsim.flows_jax import simulate_flows_batch

    kw = dict(num_hosts=16, horizon_s=0.12, dt_s=5e-4, tail_s=0.1)
    scns = [
        build_scenario("opera", "websearch", 0.1, seed=0, **kw),
        build_scenario("opera", "datamining", 0.35, seed=1, **kw),
        build_scenario("expander", "websearch", 0.2, seed=2, **kw),
        build_scenario("rotornet", "websearch", 0.15, seed=3, **kw),
    ]
    sched = FailureSchedule(
        num_racks=8, num_switches=2, seed=5,
        events=(FailureEvent("tor", (1,), onset_step=20, detect_lag=10,
                             recover_step=120),
                FailureEvent("switch", (0,), onset_step=40, detect_lag=8,
                             recover_step=200)))
    ok = True
    for label, batch in (
        ("clean", scns),
        ("faulted", [apply_flow_faults(s, sched) for s in scns[:2]] + scns[2:]),
    ):
        dense = simulate_flows_batch(batch, engine="dense")
        tiled = simulate_flows_batch(batch, engine="tiled", tile_size=64,
                                     window_tiles=2, chunk_steps=48)
        hist_ok = all(np.array_equal(d, t)
                      for d, t in zip(dense.hists, tiled.hists))
        ok &= check(f"flow {label}: histograms bitwise equal", hist_ok)
        drift = max(
            abs(d.backlog_frac - t.backlog_frac)
            for d, t in zip(dense.results, tiled.results))
        ok &= check(f"flow {label}: deficit drift < 1e-5", drift < 1e-5,
                    f"{drift:.2e}")
        fin_ok = all(d.finished_frac == t.finished_frac
                     for d, t in zip(dense.results, tiled.results))
        ok &= check(f"flow {label}: finished_frac exact", fin_ok)
        bins_off = 0.0
        for d, t in zip(dense.results, tiled.results):
            for f in ("fct_p99_ms_small", "fct_p99_ms_mid",
                      "fct_p99_ms_large"):
                dv, tv = getattr(d, f), getattr(t, f)
                if dv > 0 and np.isfinite(dv):
                    bins_off = max(
                        bins_off,
                        abs(np.log2(tv / dv)) / FCT_BIN_LOG2_WIDTH)
                else:
                    ok &= check(f"flow {label}: {f} sentinel match",
                                dv == tv, f"{dv} vs {tv}")
        ok &= check(f"flow {label}: p99s within one histogram bin",
                    bins_off <= 1.0, f"{bins_off:.2f} bins")
        rem_ok = all(
            np.allclose(d, t, rtol=1e-5, atol=1.0)
            for d, t in zip(dense.remaining_bytes, tiled.remaining_bytes))
        ok &= check(f"flow {label}: remaining bytes close", rem_ok)
    return ok


def run() -> dict:
    banner("Engine parity gates — sparse vs dense rotor, tiled vs dense flow")
    return dict(checks=dict(parity=parity_gate(),
                            flow_parity=flow_parity_gate()))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="accepted for existing callers: the gates are the "
                         "only mode")
    ap.parse_args(argv)
    if not all(run()["checks"].values()):
        sys.exit(1)


if __name__ == "__main__":
    main()
