"""Tiled streaming flow engine: dense lockstep, padding invariance,
window growth, trace guards, and the streamed-histogram statistics.

The contract: `flows_jax._tiled_step` implements the same per-step math
as the dense `_flow_step` over a sorted, tile-windowed view of the flow
state, and both accumulate completions through the shared
`_hist_accumulate` — so histograms must match *bitwise* whatever the
tile/window/chunk geometry, deficit snapshots to f32 reduction-order
tolerance, and `finalize_streamed` percentiles within one histogram
bin of the dense engine's exact ones.  Appending never-active pad
flows must leave every statistic of both engines bitwise unchanged.
"""
import dataclasses

import numpy as np
import pytest

from repro.netsim import flows
from repro.netsim.faults import (
    NEVER,
    FailureEvent,
    FailureSchedule,
    apply_flow_faults,
)
from repro.netsim.flows import (
    FCT_BIN_LOG2_WIDTH,
    FCT_HIST_BINS,
    build_scenario,
    fct_bin,
    hist_percentile,
    percentile_fct_streamed,
    saturation_load,
)
from repro.netsim.flows_jax import (
    TILED_AUTO_FLOWS,
    resolve_flow_engine,
    saturation_ladder,
    simulate_flows_batch,
)

TINY = dict(num_hosts=16, horizon_s=0.12, dt_s=5e-4, tail_s=0.1)
# deliberately tiny geometry so tile retirement, window growth, and the
# multi-chunk loop are all exercised on test-sized scenarios
TILED_KW = dict(engine="tiled", tile_size=32, window_tiles=1,
                chunk_steps=16)


def _scenarios():
    return [
        build_scenario("opera", "websearch", 0.1, seed=0, **TINY),
        build_scenario("opera", "datamining", 0.35, seed=1, **TINY),
        build_scenario("expander", "websearch", 0.2, seed=2, **TINY),
        build_scenario("rotornet", "websearch", 0.15, seed=3, **TINY),
    ]


def _sched():
    return FailureSchedule(
        num_racks=8, num_switches=2, seed=5,
        events=(FailureEvent("tor", (1,), onset_step=20, detect_lag=10,
                             recover_step=120),
                FailureEvent("switch", (0,), onset_step=40, detect_lag=8,
                             recover_step=200)))


def _faulted_scenarios():
    scns = _scenarios()
    return [apply_flow_faults(s, _sched()) for s in scns[:2]] + scns[2:]


def _assert_tiled_matches_dense(batch):
    dense = simulate_flows_batch(batch, engine="dense")
    tiled = simulate_flows_batch(batch, **TILED_KW)
    for s, d, t, dh, th, drem, trem in zip(
            batch, dense.results, tiled.results, dense.hists, tiled.hists,
            dense.remaining_bytes, tiled.remaining_bytes):
        tag = (s.network, s.workload, s.load)
        # completions flow through the shared binning math: bitwise
        assert np.array_equal(dh, th), tag
        assert d.admitted == t.admitted, tag
        assert d.finished_frac == t.finished_frac, tag
        assert abs(d.backlog_frac - t.backlog_frac) < 1e-5, tag
        np.testing.assert_allclose(trem, drem, rtol=1e-5, atol=1.0,
                                   err_msg=str(tag))
        for f in ("fct_p99_ms_small", "fct_p99_ms_mid", "fct_p99_ms_large"):
            de, ti = getattr(d, f), getattr(t, f)
            if de == 0.0 or ti == 0.0 or np.isinf(de) or np.isinf(ti):
                assert de == ti, (tag, f, de, ti)   # sentinels exact
            else:
                bins = abs(np.log2(ti / de)) / FCT_BIN_LOG2_WIDTH
                assert bins <= 1.0, (tag, f, de, ti, bins)


class TestTiledParity:
    def test_clean_grid_matches_dense(self):
        _assert_tiled_matches_dense(_scenarios())

    def test_faulted_grid_matches_dense(self):
        _assert_tiled_matches_dense(_faulted_scenarios())

    def test_window_growth_is_invisible(self):
        """Starting from a 1-tile window forces capacity doubling; the
        grown run must agree bitwise on histograms with a run whose
        window was ample from the start."""
        scns = _scenarios()
        small = simulate_flows_batch(scns, engine="tiled", tile_size=32,
                                     window_tiles=1, chunk_steps=16)
        ample = simulate_flows_batch(scns, engine="tiled", tile_size=32,
                                     window_tiles=64, chunk_steps=16)
        assert small.peak_window_tiles > 1
        assert small.peak_window_tiles == ample.peak_window_tiles
        for a, b in zip(small.hists, ample.hists):
            assert np.array_equal(a, b)
        # backlog_frac is (rem_end - rem_mid) / offered over two float32
        # device sums of the (W, T) window.  XLA picks a reduction's
        # association by the operand's shape, so a grown window (another
        # W) re-associates the sum: allow 32 float32 ulps of it.  Every
        # other field is computed from bitwise-equal state and must match.
        for a, b in zip(small.results, ample.results):
            assert a.backlog_frac == pytest.approx(b.backlog_frac,
                                                   rel=32 * 2.0**-24)
            assert dataclasses.replace(a, backlog_frac=b.backlog_frac) == b


def _pad(scn, npad=37):
    """Append `npad` never-active flows: zero bytes, activation beyond
    the scan, NEVER fault windows."""
    pads = dict(
        arr=np.full(npad, scn.horizon_s, scn.arr.dtype),
        sizes=np.zeros(npad, scn.sizes.dtype),
        start_step=np.full(npad, scn.steps + 1, scn.start_step.dtype),
        is_bulk=np.zeros(npad, scn.is_bulk.dtype),
    )
    if scn.has_faults:
        for f in ("blk_start", "blk_end", "frz_start", "frz_end"):
            pads[f] = np.full(npad, NEVER, getattr(scn, f).dtype)
    return dataclasses.replace(scn, **{
        f: np.concatenate([getattr(scn, f), v]) for f, v in pads.items()
    })


class TestPaddingInvariance:
    @pytest.mark.parametrize("faulted", [False, True])
    @pytest.mark.parametrize("engine_kw", [dict(engine="dense"), TILED_KW],
                             ids=["dense", "tiled"])
    def test_pad_flows_change_nothing(self, faulted, engine_kw):
        scns = _faulted_scenarios() if faulted else _scenarios()
        a = simulate_flows_batch(scns, **engine_kw)
        b = simulate_flows_batch([_pad(s) for s in scns], **engine_kw)
        for i, s in enumerate(scns):
            n = s.num_flows
            assert a.results[i] == b.results[i], (i, s.network, s.workload)
            assert np.array_equal(a.hists[i], b.hists[i])
            assert np.array_equal(a.remaining_bytes[i],
                                  b.remaining_bytes[i][:n])
            assert np.all(b.remaining_bytes[i][n:] == 0.0)


class TestGuardsAndDispatch:
    def test_bad_engine_rejected(self):
        scn = build_scenario("opera", "websearch", 0.1, seed=0, **TINY)
        with pytest.raises(ValueError, match="engine must be"):
            simulate_flows_batch([scn], engine="sparse")

    def test_trace_is_dense_only(self):
        scn = build_scenario("opera", "websearch", 0.1, seed=0, **TINY)
        with pytest.raises(ValueError, match="dense-only"):
            simulate_flows_batch([scn], engine="tiled", trace=True)

    def test_trace_size_gate(self, monkeypatch):
        import repro.netsim.flows_jax as fj

        scn = build_scenario("opera", "websearch", 0.1, seed=0, **TINY)
        monkeypatch.setattr(fj, "TRACE_MAX_ELEMS", 100)
        with pytest.raises(ValueError, match="TRACE_MAX_ELEMS"):
            fj.simulate_flows_batch([scn], trace=True)

    def test_auto_resolution(self):
        assert resolve_flow_engine("auto", 100) == "dense"
        assert resolve_flow_engine("auto", TILED_AUTO_FLOWS) == "tiled"
        # trace mode pins auto to dense whatever the size
        assert resolve_flow_engine("auto", TILED_AUTO_FLOWS,
                                   trace=True) == "dense"
        assert resolve_flow_engine("dense", TILED_AUTO_FLOWS) == "dense"
        assert resolve_flow_engine("tiled", 100) == "tiled"


class TestStreamedStatistics:
    def test_hist_percentile_tracks_numpy(self):
        """Rank-interpolated histogram quantiles stay within one
        log-spaced bin of numpy's exact percentile."""
        rng = np.random.default_rng(11)
        for scale in (0.05, 1.0, 40.0):
            vals = np.clip(rng.lognormal(np.log(scale), 1.2, 4000),
                           2e-2, 5e4)
            hist = np.bincount(fct_bin(vals), minlength=FCT_HIST_BINS)
            for q in (50.0, 90.0, 99.0):
                exact = float(np.percentile(vals, q))
                est = hist_percentile(hist, q)
                bins = abs(np.log2(est / exact)) / FCT_BIN_LOG2_WIDTH
                assert bins <= 1.0, (scale, q, exact, est, bins)

    def test_hist_percentile_empty_is_nan(self):
        assert np.isnan(hist_percentile(np.zeros(FCT_HIST_BINS, np.int64),
                                        99.0))

    def test_streamed_percentile_sentinels(self):
        """Same admission semantics as the exact `percentile_fct`: no
        flows in class -> 0.0, nothing finished -> inf, too few
        completions under saturation -> inf."""
        hist = np.zeros(FCT_HIST_BINS, np.int64)
        assert percentile_fct_streamed(hist, 0, 0) == 0.0
        assert np.isinf(percentile_fct_streamed(hist, 10, 0))
        hist[40] = 3
        assert np.isinf(percentile_fct_streamed(hist, 100, 3))
        hist[40] = 200
        assert np.isfinite(percentile_fct_streamed(hist, 200, 200))


class TestLadders:
    def test_duplicate_loads_grouped_by_index(self):
        """Regression: row grouping is positional, so ladder loads that
        collide in float (or repeat exactly) still yield one row per
        (load, seed) slot."""
        rows = saturation_ladder("opera", "websearch",
                                 [0.04, 0.04, 0.25], seeds=(0,), **TINY)
        assert len(rows) == 3
        assert [r["load"] for r in rows] == [0.04, 0.04, 0.25]
        assert rows[0]["admitted_frac"] == rows[1]["admitted_frac"]

    def test_saturation_knee_engine_parity(self):
        kw = dict(ceiling=0.4, coarse_points=4, refine_points=3,
                  seeds=(0,), **TINY)
        dense = saturation_load("opera", "websearch", engine="dense", **kw)
        tiled = saturation_load("opera", "websearch", engine="tiled", **kw)
        assert dense.load == tiled.load
        assert dense.beyond_grid == tiled.beyond_grid
