"""Batched JAX flow-level engines (Figs. 7, 9, 10): dense and tiled.

Re-expresses `flows.simulate`'s fixed-dt processor-sharing recurrence as
jitted `lax.scan` programs with `jax.vmap` over a leading scenario axis
— the (network x workload x load x seed) grids the paper's FCT-vs-load
and saturation figures sweep — behind an `engine="auto"|"dense"|"tiled"`
switch mirroring `fluid_jax`'s dense/sparse dispatch:

  dense  — flow state held as (B, n_max) tensors for the whole horizon;
           one compiled call simulates the whole grid.  Exact per-flow
           completion steps; supports `trace=True` (test-sized grids).
  tiled  — flows sorted by activation step and packed into fixed-size
           tiles; each device dispatch scans `chunk_steps` steps over
           only the (B, window, tile) *active window* of tiles (a
           two-pass per-step reduction: per-tile active counts ->
           global pool share -> per-tile service apply).  Tiles leave
           the window when fully drained, so per-step work and peak
           device state track the concurrently-active flow population
           instead of the scenario's whole lifetime — the regime that
           makes millions of mostly-short flows affordable.  FCT
           percentiles stream out of log-binned on-device histograms
           (`flows.finalize_streamed`); per-flow `done_step` never
           round-trips to the host.

The per-step math is numerically identical to the numpy oracle
(`flows._oracle_steps`) and is lockstep-tested by tests/test_flows_jax.py
and tests/test_flows_tiled.py; the dense and tiled engines share
`_hist_accumulate`, so their completion histograms agree bitwise.

Internals: byte quantities are normalized to one NIC-step of service
(`nic_Bps * dt`) so float32 keeps ample mantissa headroom; activation
times are pre-discretized to int32 step indices on the host (shared
with the oracle via `flows.FlowScenario`), so there is no float time
comparison on the device.  The dense engine gathers the half-horizon /
horizon service-deficit snapshots against host-precomputed NIC-bound
allowances (`FlowScenario.deficit_allowance`); the tiled engine
recomputes the same allowance on device (in normalized units a
dedicated NIC serves exactly 1.0 per step), because flows outside the
window contribute zero deficit by construction.  Scenarios with fewer
flows than the batch maximum are padded with never-active flows
(remaining = 0, start step beyond the scan); `flows.finalize` ignores
zero-size flows, so padding never shifts a result.  Tiled chunk
programs are shaped by (batch, window, tile, chunk_steps) only — never
by the scenario's flow count — so one lowering serves every load and
seed of a design point (pinned by staticcheck's
`count_tiled_lowerings`).

Faults: each engine is one jitted program whose last operand is an
optional `FlowFaults` pytree (`faults.apply_flow_faults` windows and
pool scales).  ``None`` is resolved at trace time, so the failure-free
lowering carries no fault work at all; jit keys its cache on the
operand's pytree structure, so every failure draw shares the one
faulted lowering.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.netsim.flows import (
    FCT_BIN_LOG2_WIDTH,
    FCT_HIST_BINS,
    FCT_HIST_LO_LOG2,
    NUM_FCT_CLASSES,
    FlowScenario,
    FlowSimResult,
    build_scenario,
    fct_class_id,
    finalize,
    finalize_streamed,
)

# engine="auto" stays dense below this many flows (largest scenario in
# the batch): the dense scan is a single dispatch with no host-side
# chunk loop, which wins while the whole state fits comfortably.
TILED_AUTO_FLOWS = 65536
# trace=True materializes a (B, steps, n_max) float stack; refuse
# clearly above this many elements instead of OOMing deep in XLA.
TRACE_MAX_ELEMS = 1 << 26
# Tiled-engine geometry defaults: tiles of 1024 flows, a window that
# starts at 16 tiles and grows by powers of two on demand, and 128
# scan steps per device dispatch.
DEFAULT_TILE = 1024
DEFAULT_WINDOW_TILES = 16
DEFAULT_CHUNK_STEPS = 128


def resolve_flow_engine(engine: str, n_max: int, trace: bool = False) -> str:
    """'auto' -> 'dense'|'tiled' by scenario size (trace forces dense)."""
    if engine == "auto":
        return "dense" if (trace or n_max < TILED_AUTO_FLOWS) else "tiled"
    if engine not in ("dense", "tiled"):
        raise ValueError(f"engine must be auto|dense|tiled, got {engine!r}")
    return engine


class FlowOps(NamedTuple):
    """The flow programs' per-scenario operands, each with a leading
    scenario axis: per-flow arrays (``rem0`` remaining bytes at start,
    activation step, bulk flag, FCT class, arrival ms; the dense engine's
    NIC-bound deficit allowances) and per-scenario scalars (pool
    capacities, dt, the two deficit-snapshot steps)."""

    rem0: jax.Array
    start: jax.Array
    is_bulk: jax.Array
    class_id: jax.Array
    arr_ms: jax.Array
    lat_u: jax.Array
    bulk_u: jax.Array
    dt_ms: jax.Array
    mid_step: jax.Array
    end_step: jax.Array
    allow_mid: Optional[jax.Array] = None   # dense engine only
    allow_end: Optional[jax.Array] = None   # dense engine only


class FlowFaults(NamedTuple):
    """The faulted programs' extra operand (`faults.apply_flow_faults`):
    per-flow blackhole/frozen windows, shaped like the flow arrays, and
    per-step pool-capacity scales (B, steps), scanned with the steps —
    inside a step body each scale is that step's scalar."""

    blk_start: jax.Array
    blk_end: jax.Array
    frz_start: jax.Array
    frz_end: jax.Array
    lat_scale: jax.Array
    bulk_scale: jax.Array


def _hist_accumulate(hist, fct_sum, newly, class_id, step, arr_ms, dt_ms):
    """Scatter newly-finished flows into the flat per-class log-spaced
    FCT histogram (`flows.fct_bin`'s device twin) and accumulate the
    completion-time sum.  Shared by the dense and tiled scan bodies, so
    their histograms agree bitwise."""
    fct_ms = dt_ms * (step + 1) - arr_ms
    safe = jnp.where(newly, fct_ms, 1.0)
    b = jnp.floor((jnp.log2(safe) - FCT_HIST_LO_LOG2)
                  * (1.0 / FCT_BIN_LOG2_WIDTH))
    b = jnp.clip(b, 0, FCT_HIST_BINS - 1).astype(jnp.int32)
    idx = (class_id * FCT_HIST_BINS + b).reshape(-1)
    hist = hist.at[idx].add(newly.reshape(-1).astype(hist.dtype))
    fct_sum = fct_sum + jnp.where(newly, fct_ms, 0.0).sum()
    return hist, fct_sum


def _fault_gates(step, active, faults):
    """(sharing, blackhole) masks of one step.  Under `faults`, frozen
    flows (detected-dead ToR) leave the share computation and
    blackholed flows (dead circuit, pre-detection) consume their share
    with zero progress; without, every active flow shares and none is
    lost (``blackhole`` None)."""
    if faults is None:
        return active, None
    frozen = (step >= faults.frz_start) & (step < faults.frz_end)
    blackhole = (step >= faults.blk_start) & (step < faults.blk_end)
    return active & ~frozen, blackhole


def _serve_pools(rem, sharing, blackhole, ops, faults, count):
    """Serve the latency pool, then the bulk pool, for one step: each
    pool (scaled by the step's surviving-capacity fraction under
    `faults`) is split evenly over its sharing flows, `count` of them,
    at most one NIC-step (1.0) a flow.  Yields each pool's member mask
    and the remaining bytes after it is served."""
    scales = ((None, None) if faults is None
              else (faults.lat_scale, faults.bulk_scale))
    for pool_u, scale, mask in (
        (ops.lat_u, scales[0], sharing & ~ops.is_bulk),
        (ops.bulk_u, scales[1], sharing & ops.is_bulk),
    ):
        if scale is not None:
            pool_u = pool_u * scale
        m = mask.astype(rem.dtype)
        k = count(m)
        share = jnp.minimum(pool_u / jnp.maximum(k, 1.0), 1.0)
        share = jnp.where(pool_u > 0, share, 0.0)
        prog = (m if blackhole is None
                else (mask & ~blackhole).astype(rem.dtype))
        rem = rem - jnp.minimum(rem, share) * prog
        yield mask, rem


def _scan_steps(step_fn, carry, steps, faults):
    """`lax.scan` of ``step_fn(carry, step)`` over `steps`; with `faults`
    the pool scales are scanned alongside and each call gets
    ``step_fn(carry, step, faults)`` with that step's scales."""
    if faults is None:
        return jax.lax.scan(step_fn, carry, steps)

    def body(c, xs):
        step, lat, bulk = xs
        return step_fn(c, step, faults._replace(lat_scale=lat, bulk_scale=bulk))

    return jax.lax.scan(body, carry,
                        (steps, faults.lat_scale, faults.bulk_scale))


def _flow_step(carry, step, ops, trace: bool, faults=None):
    """One fixed-dt step, pure jnp — the dense scan body.

    Mirrors `flows._oracle_steps` exactly (normalized units: every
    flow's per-step NIC budget is 1.0), and its faulted branch when
    `faults` (this step's slice of a `FlowFaults`) is given: windows
    are data (int32 comparisons), so one lowering serves every failure
    draw.  Change the two together.
    """
    remaining, done_step, rem_mid, rem_end, hist, fct_sum = carry
    active = (step >= ops.start) & (remaining > 0)
    sharing, blackhole = _fault_gates(step, active, faults)
    # Deficit snapshots stay per-flow vectors; the host sums them at
    # float64 over real flows only, so appending never-active pad flows
    # is bitwise invisible (no device reduction to regroup).
    rem_mid = jnp.where(
        step == ops.mid_step,
        jnp.maximum(remaining - ops.allow_mid, 0.0), rem_mid,
    )
    rem_end = jnp.where(
        step == ops.end_step,
        jnp.maximum(remaining - ops.allow_end, 0.0), rem_end,
    )
    newly_any = jnp.zeros(remaining.shape, bool)
    for mask, remaining in _serve_pools(remaining, sharing, blackhole,
                                        ops, faults, jnp.sum):
        newly = mask & (remaining <= 0) & (done_step < 0)
        done_step = jnp.where(newly, step + 1, done_step)
        newly_any = newly_any | newly
    hist, fct_sum = _hist_accumulate(
        hist, fct_sum, newly_any, ops.class_id, step, ops.arr_ms, ops.dt_ms
    )
    carry = (remaining, done_step, rem_mid, rem_end, hist, fct_sum)
    return carry, (remaining if trace else jnp.zeros((), remaining.dtype))


@functools.partial(jax.jit, static_argnames=("num_steps", "trace"))
def _run_batch(scn: FlowOps, num_steps: int, trace: bool,
               faults: Optional[FlowFaults] = None):
    """vmap(scan): batch -> time steps over the (B, n_max) flow state.
    Every operand carries a leading scenario axis; `faults` adds the
    fault windows (B, n_max) and pool scales (B, num_steps)."""

    def one_scenario(ops, f):
        rem0 = ops.rem0
        carry0 = (
            rem0,
            jnp.full(rem0.shape, -1, jnp.int32),
            jnp.zeros(rem0.shape, rem0.dtype),
            jnp.zeros(rem0.shape, rem0.dtype),
            jnp.zeros(NUM_FCT_CLASSES * FCT_HIST_BINS, jnp.int32),
            jnp.zeros((), rem0.dtype),
        )
        steps = jnp.arange(num_steps, dtype=jnp.int32)
        (remaining, done_step, rem_mid, rem_end, hist, fct_sum), ys = (
            _scan_steps(lambda c, s, *f_t: _flow_step(c, s, ops, trace, *f_t),
                        carry0, steps, f)
        )
        return remaining, done_step, rem_mid, rem_end, hist, fct_sum, ys

    return jax.vmap(one_scenario)(scn, faults)


# ---------------- tiled streaming engine -------------------------------


def _tiled_step(carry, step, ops, faults=None):
    """One fixed-dt step over the (window, tile) active slice — the
    tiled scan body; ``ops`` is (`FlowOps` row, horizon in steps).
    Identical per-flow math to `_flow_step` / `flows._oracle_steps`,
    faulted branch included (change them together); the two-pass
    reduction (per-tile counts -> global share -> per-tile apply) only
    regroups exact small-integer sums, so shares and therefore
    remaining-byte trajectories and histograms match the dense engine
    bitwise.  `live` gates steps past the scenario horizon in the final
    partial chunk."""
    rem, hist, fct_sum, rem_mid, rem_end = carry
    scn, num_steps = ops
    live = step < num_steps
    active = live & (step >= scn.start) & (rem > 0)
    sharing, blackhole = _fault_gates(step, active, faults)
    # NIC-bound deficit allowance on device (normalized units: a
    # dedicated NIC serves exactly 1.0 per step).  Flows outside the
    # window contribute zero deficit: drained tiles have rem == 0,
    # future tiles rem == rem0 == allow.
    allow = scn.rem0 - jnp.minimum(
        scn.rem0, jnp.maximum(step - scn.start, 0).astype(rem.dtype)
    )
    deficit = jnp.maximum(rem - allow, 0.0).sum()
    rem_mid = jnp.where(live & (step == scn.mid_step), deficit, rem_mid)
    rem_end = jnp.where(live & (step == scn.end_step), deficit, rem_end)
    newly_any = jnp.zeros(rem.shape, bool)
    for mask, rem in _serve_pools(
        rem, sharing, blackhole, scn, faults,
        lambda m: m.sum(axis=-1).sum(),   # per-tile counts -> global pool
    ):
        newly_any = newly_any | (mask & (rem <= 0))
    hist, fct_sum = _hist_accumulate(
        hist, fct_sum, newly_any, scn.class_id, step, scn.arr_ms, scn.dt_ms
    )
    return (rem, hist, fct_sum, rem_mid, rem_end)


@functools.partial(jax.jit, static_argnames=("num_steps", "chunk_steps"))
def _run_tiled_chunk(carry, scn: FlowOps, step0, num_steps: int,
                     chunk_steps: int, faults: Optional[FlowFaults] = None):
    """`chunk_steps` scan steps over the (B, W, T) active windows, one
    device dispatch.  ``carry`` is (rem, hist, fct_sum, rem_mid,
    rem_end): histograms and deficit snapshots stay device-resident
    across chunks; only the window's remaining bytes round-trip to the
    host (for tile retirement).  `faults` adds the windows' fault
    windows (B, W, T) and this chunk's pool scales (B, chunk_steps).
    Shapes depend on the window geometry only — never on the scenario's
    total flow count — so one lowering serves every load and seed of a
    design point."""
    steps = step0 + jnp.arange(chunk_steps, dtype=jnp.int32)

    def one_scenario(c0, scn_row, f):
        ops = (scn_row, num_steps)
        c, _ = _scan_steps(
            lambda c, s, *f_t: (_tiled_step(c, s, ops, *f_t), None),
            c0, steps, f)
        return c

    return jax.vmap(one_scenario)(carry, scn, faults)


class _TiledState:
    """Host-side per-scenario tiled flow state: flows stably sorted by
    activation step, padded to whole tiles, with a monotone window
    [lo, hi) of not-yet-drained tiles that have (or are about to have)
    arrivals.  Because the sort is by start step, the window is always
    a contiguous tile range — plain numpy slices, no gathers."""

    def __init__(self, scn: FlowScenario, tile: int, num_steps: int,
                 faulted: bool):
        n = scn.num_flows
        self.tile = tile
        self.n = n
        self.unit = scn.nic_Bps * scn.dt_s
        self.order = np.argsort(scn.start_step, kind="stable")
        self.ntiles = max(-(-n // tile), 1)
        P = self.ntiles * tile
        sizes = scn.sizes[self.order]
        rem64 = np.zeros(P, np.float64)     # staticcheck: ok SC-AST-F64 (host staging)
        rem64[:n] = sizes / self.unit
        self.rem = rem64.astype(np.float32)
        self.rem0 = self.rem.copy()
        self.start = np.full(P, num_steps + 1, np.int32)
        self.start[:n] = scn.start_step[self.order]
        self.is_bulk = np.zeros(P, bool)
        self.is_bulk[:n] = scn.is_bulk[self.order]
        self.class_id = np.zeros(P, np.int32)
        self.class_id[:n] = fct_class_id(sizes)
        arr64 = np.zeros(P, np.float64)     # staticcheck: ok SC-AST-F64 (host staging)
        arr64[:n] = scn.arr[self.order] * 1e3
        self.arr_ms = arr64.astype(np.float32)
        # first activation step per tile — non-decreasing (sorted), so
        # the window's upper edge is a searchsorted; pad-only tiles
        # activate "never" and are skipped outright.
        self.tile_first_start = self.start.reshape(self.ntiles, tile)[:, 0].copy()
        self.lo = 0
        if faulted:
            from repro.netsim.faults import flow_fault_arrays

            (self.blk_start, self.blk_end, self.frz_start, self.frz_end,
             self.lat_scale, self.bulk_scale) = flow_fault_arrays(
                scn, num_steps, order=self.order, pad_to=P)

    def window(self, chunk_end: int) -> int:
        """Tiles in [lo, hi) where hi counts tiles with any flow
        activating before `chunk_end`."""
        hi = int(np.searchsorted(self.tile_first_start, chunk_end, "left"))
        return max(hi - self.lo, 0)

    def fill(self, row: Dict[str, np.ndarray], b: int, w: int) -> None:
        t0 = self.lo * self.tile
        sl = slice(t0, t0 + w * self.tile)
        shape = (w, self.tile)
        for name in row:
            row[name][b, :w] = getattr(self, name)[sl].reshape(shape)

    def writeback(self, rem_rows: np.ndarray, w: int) -> None:
        if w:
            t0 = self.lo * self.tile
            self.rem[t0:t0 + w * self.tile] = rem_rows[:w].reshape(-1)

    def advance(self) -> None:
        """Retire the contiguous prefix of fully-drained tiles."""
        while self.lo < self.ntiles:
            sl = slice(self.lo * self.tile, (self.lo + 1) * self.tile)
            if np.all(self.rem[sl] == 0.0):
                self.lo += 1
            else:
                break

    @property
    def done(self) -> bool:
        return self.lo >= self.ntiles

    def remaining_bytes(self) -> np.ndarray:
        """(n,) remaining bytes in the scenario's original flow order."""
        out = np.zeros(self.n, np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
        out[self.order] = self.rem[:self.n]
        return out * self.unit


def _simulate_flows_tiled(
    scenarios: Sequence[FlowScenario],
    dtype,
    tile_size: int,
    window_tiles: int,
    chunk_steps: int,
) -> "FlowBatchResult":
    """The tiled streaming engine's host driver: a chunk loop that
    assembles each scenario's active window into a shared (B, W, T)
    buffer, dispatches one jitted multi-step chunk, writes the surviving
    remaining bytes back, and retires drained tiles.  The window
    capacity W grows by powers of two when any scenario's active window
    outgrows it (monotone, so a design point compiles a handful of
    geometries at most); chunks where every window is empty are skipped
    without a dispatch."""
    num_steps = scenarios[0].steps
    B, T, C = len(scenarios), int(tile_size), int(chunk_steps)
    faulted = any(s.has_faults for s in scenarios)
    with obs.span("flows.prepare"):
        states = [_TiledState(s, T, num_steps, faulted) for s in scenarios]
        scalars = dict(
            lat_u=jnp.asarray([s.lat_pool_Bps / s.nic_Bps for s in scenarios], dtype),
            bulk_u=jnp.asarray([s.bulk_pool_Bps / s.nic_Bps for s in scenarios], dtype),
            dt_ms=jnp.asarray([s.dt_s * 1e3 for s in scenarios], dtype),
            mid_step=jnp.asarray([s.mid_step for s in scenarios], jnp.int32),
            end_step=jnp.asarray([s.end_step for s in scenarios], jnp.int32),
        )
        obs.count("flows.h2d_bytes", sum(x.nbytes for x in scalars.values()))
        hist = jnp.zeros((B, NUM_FCT_CLASSES * FCT_HIST_BINS), jnp.int32)
        fct_sum = jnp.zeros((B,), dtype)
        rem_mid = jnp.zeros((B,), dtype)
        rem_end = jnp.zeros((B,), dtype)

    if faulted:
        from repro.netsim.faults import NEVER

    W = int(window_tiles)
    peak_w = 0
    c0 = 0
    with obs.span("flows.run"):
        while c0 < num_steps:
            chunk_end = min(c0 + C, num_steps)
            ws = [st.window(chunk_end) for st in states]
            peak_w = max(peak_w, max(ws))
            if max(ws) == 0:
                if all(st.done for st in states):
                    break
                c0 += C
                continue
            while max(ws) > W:
                W *= 2
            with obs.span("flows.tiled.fill"):
                row = dict(
                    rem=np.zeros((B, W, T), np.float32),
                    rem0=np.zeros((B, W, T), np.float32),
                    start=np.full((B, W, T), num_steps + 1, np.int32),
                    is_bulk=np.zeros((B, W, T), bool),
                    class_id=np.zeros((B, W, T), np.int32),
                    arr_ms=np.zeros((B, W, T), np.float32),
                )
                if faulted:
                    for name in ("blk_start", "blk_end", "frz_start", "frz_end"):
                        row[name] = np.full((B, W, T), NEVER, np.int32)
                for b, (st, w) in enumerate(zip(states, ws)):
                    st.fill(row, b, w)
                if faulted:
                    lsc = row["lat_scale"] = np.ones((B, C), np.float32)
                    bsc = row["bulk_scale"] = np.ones((B, C), np.float32)
                    for b, st in enumerate(states):
                        lsc[b, :chunk_end - c0] = st.lat_scale[c0:chunk_end]
                        bsc[b, :chunk_end - c0] = st.bulk_scale[c0:chunk_end]
            with obs.span("flows.tiled.upload"):
                # float arrays go up at the engine dtype
                up = {name: jnp.asarray(a, dtype) if a.dtype == np.float32
                      else jnp.asarray(a) for name, a in row.items()}
                obs.count("flows.h2d_bytes", sum(x.nbytes for x in up.values()))
                scn = FlowOps(rem0=up["rem0"], start=up["start"],
                              is_bulk=up["is_bulk"], class_id=up["class_id"],
                              arr_ms=up["arr_ms"], **scalars)
                faults = (FlowFaults(**{f: up[f] for f in FlowFaults._fields})
                          if faulted else None)
            with obs.span("flows.tiled.chunk"):
                rem_out, hist, fct_sum, rem_mid, rem_end = _run_tiled_chunk(
                    (up["rem"], hist, fct_sum, rem_mid, rem_end), scn, c0,
                    num_steps=num_steps, chunk_steps=C, faults=faults,
                )
            with obs.span("flows.tiled.readback"):
                rem_np = np.asarray(rem_out)
            with obs.span("flows.tiled.retire"):
                for b, (st, w) in enumerate(zip(states, ws)):
                    st.writeback(rem_np[b], w)
                    st.advance()
            c0 += C

    with obs.span("flows.readback"):
        hists = np.asarray(hist, np.int64).reshape(
            B, NUM_FCT_CLASSES, FCT_HIST_BINS
        )
        fct_sums = np.asarray(fct_sum, np.float64)   # staticcheck: ok SC-AST-F64 (host staging)
        units = np.asarray([st.unit for st in states])
        rem_mid_B = np.asarray(rem_mid, np.float64) * units  # staticcheck: ok SC-AST-F64 (host staging)
        rem_end_B = np.asarray(rem_end, np.float64) * units  # staticcheck: ok SC-AST-F64 (host staging)
    with obs.span("flows.finalize"):
        results = [
            finalize_streamed(s, hists[b], float(fct_sums[b]),
                              rem_mid_B[b], rem_end_B[b])
            for b, s in enumerate(scenarios)
        ]
        remaining_bytes = [st.remaining_bytes() for st in states]
    return FlowBatchResult(
        results, remaining_bytes, traces=None,
        hists=[hists[b] for b in range(B)],
        peak_window_tiles=peak_w,
    )


@dataclasses.dataclass
class FlowBatchResult:
    """Batched engine output: one `FlowSimResult` per scenario (dense:
    `flows.finalize` on exact completion steps; tiled:
    `flows.finalize_streamed` on the device histograms), the per-flow
    remaining bytes at scan end (fig10 integrates these into served
    throughput), each scenario's (classes, bins) completion-time
    histogram, and — dense trace mode, test-sized grids only — each
    scenario's full (steps, n) remaining-bytes trajectory."""

    results: List[FlowSimResult]
    remaining_bytes: List[np.ndarray]       # (n_b,) per scenario
    traces: Optional[List[np.ndarray]] = None
    hists: Optional[List[np.ndarray]] = None
    peak_window_tiles: Optional[int] = None  # tiled engine only


def simulate_flows_batch(
    scenarios: Sequence[FlowScenario],
    dtype=jnp.float32,
    trace: bool = False,
    engine: str = "auto",
    tile_size: int = DEFAULT_TILE,
    window_tiles: int = DEFAULT_WINDOW_TILES,
    chunk_steps: int = DEFAULT_CHUNK_STEPS,
) -> FlowBatchResult:
    """Simulate a batch of flow scenarios on the dense or tiled engine.

    All scenarios must share dt/horizon/tail (one static step count per
    compiled program); flow counts may differ — shorter rows are padded
    with never-active flows.  Rows carrying a fault projection
    (`faults.apply_flow_faults`) give the whole batch a `FlowFaults`
    operand; fault-free batches run without one, as the failure-free
    lowering (bit-identical no-op dispatch).  `engine="auto"` picks
    tiled once the largest scenario reaches `TILED_AUTO_FLOWS` flows
    (trace mode forces dense and is size-gated by `TRACE_MAX_ELEMS`).
    """
    if not scenarios:
        return FlowBatchResult([], [])
    steps = {s.steps for s in scenarios}
    if len(steps) != 1:
        raise ValueError(f"scenarios disagree on step count: {sorted(steps)}")
    num_steps = steps.pop()
    n_max = max(s.num_flows for s in scenarios)
    B = len(scenarios)
    resolved = resolve_flow_engine(engine, n_max, trace)
    if trace:
        if resolved != "dense":
            raise ValueError("trace=True is dense-only: the tiled engine "
                             "never materializes per-flow trajectories")
        elems = B * num_steps * n_max
        if elems > TRACE_MAX_ELEMS:
            raise ValueError(
                f"trace=True would materialize a ({B}, {num_steps}, "
                f"{n_max}) remaining-bytes stack ({elems:,} elements > "
                f"TRACE_MAX_ELEMS={TRACE_MAX_ELEMS:,}); trace mode is for "
                "test-sized grids — drop trace or shrink the scenario"
            )
    obs.count("flows.scenario_steps", B * num_steps)
    if resolved == "tiled":
        return _simulate_flows_tiled(
            scenarios, dtype, tile_size, window_tiles, chunk_steps
        )

    with obs.span("flows.prepare"):
        # Host-side staging is float64 on purpose: oracle-shared quantities
        # are normalized at full precision, then cast once at the device
        # boundary.
        remaining0 = np.zeros((B, n_max), np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
        start_step = np.full((B, n_max), num_steps + 1, np.int32)
        is_bulk = np.zeros((B, n_max), bool)
        allow_mid = np.zeros((B, n_max), np.float64)   # staticcheck: ok SC-AST-F64 (host staging)
        allow_end = np.zeros((B, n_max), np.float64)   # staticcheck: ok SC-AST-F64 (host staging)
        class_id = np.zeros((B, n_max), np.int32)
        arr_ms = np.zeros((B, n_max), np.float64)      # staticcheck: ok SC-AST-F64 (host staging)
        lat_u = np.zeros(B)
        bulk_u = np.zeros(B)
        dt_ms = np.zeros(B)
        mid_step = np.zeros(B, np.int32)
        end_step = np.zeros(B, np.int32)
        units = np.zeros(B)
        faulted = any(s.has_faults for s in scenarios)
        if faulted:
            # NEVER-filled windows for fault-free rows and pad flows; unit
            # scales for fault-free rows — the faulted step then computes
            # the plain recurrence for them (x * 1.0, never-true windows).
            from repro.netsim.faults import NEVER

            blk_start = np.full((B, n_max), NEVER, np.int32)
            blk_end = np.full((B, n_max), NEVER, np.int32)
            frz_start = np.full((B, n_max), NEVER, np.int32)
            frz_end = np.full((B, n_max), NEVER, np.int32)
            lat_scale = np.ones((B, num_steps), np.float64)   # staticcheck: ok SC-AST-F64 (host staging)
            bulk_scale = np.ones((B, num_steps), np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
        for b, s in enumerate(scenarios):
            n = s.num_flows
            unit = s.nic_Bps * s.dt_s          # bytes one NIC serves per step
            units[b] = unit
            remaining0[b, :n] = s.sizes / unit
            start_step[b, :n] = s.start_step
            is_bulk[b, :n] = s.is_bulk
            allow_mid[b, :n] = s.deficit_allowance(s.mid_step) / unit
            allow_end[b, :n] = s.deficit_allowance(s.end_step) / unit
            class_id[b, :n] = fct_class_id(s.sizes)
            arr_ms[b, :n] = s.arr * 1e3
            lat_u[b] = s.lat_pool_Bps / s.nic_Bps
            bulk_u[b] = s.bulk_pool_Bps / s.nic_Bps
            dt_ms[b] = s.dt_s * 1e3
            mid_step[b] = s.mid_step
            end_step[b] = s.end_step
            if faulted and s.has_faults:
                blk_start[b, :n] = s.blk_start
                blk_end[b, :n] = s.blk_end
                frz_start[b, :n] = s.frz_start
                frz_end[b, :n] = s.frz_end
                lat_scale[b] = s.lat_scale[:num_steps]
                bulk_scale[b] = s.bulk_scale[:num_steps]

        scn = FlowOps(
            rem0=jnp.asarray(remaining0, dtype),
            start=jnp.asarray(start_step),
            is_bulk=jnp.asarray(is_bulk),
            class_id=jnp.asarray(class_id),
            arr_ms=jnp.asarray(arr_ms, dtype),
            lat_u=jnp.asarray(lat_u, dtype),
            bulk_u=jnp.asarray(bulk_u, dtype),
            dt_ms=jnp.asarray(dt_ms, dtype),
            mid_step=jnp.asarray(mid_step),
            end_step=jnp.asarray(end_step),
            allow_mid=jnp.asarray(allow_mid, dtype),
            allow_end=jnp.asarray(allow_end, dtype),
        )
        faults = None
        if faulted:
            faults = FlowFaults(
                jnp.asarray(blk_start), jnp.asarray(blk_end),
                jnp.asarray(frz_start), jnp.asarray(frz_end),
                jnp.asarray(lat_scale, dtype), jnp.asarray(bulk_scale, dtype),
            )
        obs.count("flows.h2d_bytes",
                  sum(x.nbytes for x in jax.tree.leaves((scn, faults))))

    with obs.span("flows.run"):
        remaining, done_step, rem_mid, rem_end, hist, _, ys = _run_batch(
            scn, num_steps, bool(trace), faults)
    with obs.span("flows.readback"):
        done_step = np.asarray(done_step)
        # Device f32 results are de-normalized on the host at float64,
        # matching the float64 oracle's finalize() inputs.  The deficit
        # snapshots come back as per-flow vectors and are summed here over
        # *real* flows only: the summed arrays are then identical whether or
        # not never-active pad flows were appended, so padding is bitwise
        # invisible.
        remaining = np.asarray(remaining, np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
        rem_mid = np.asarray(rem_mid, np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
        rem_end = np.asarray(rem_end, np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
        hist = np.asarray(hist, np.int64).reshape(B, NUM_FCT_CLASSES, FCT_HIST_BINS)
        if trace:
            # staticcheck: ok SC-AST-F64 (host staging)
            ys = np.asarray(ys, np.float64)    # (B, steps, n_max)

    with obs.span("flows.finalize"):
        def _deficit(vec, b, s):
            real = s.sizes > 0
            return float(vec[b, : s.num_flows][real].sum()) * units[b]

        results = [
            finalize(s, done_step[b, : s.num_flows],
                     _deficit(rem_mid, b, s), _deficit(rem_end, b, s))
            for b, s in enumerate(scenarios)
        ]
        remaining_bytes = [
            remaining[b, : s.num_flows] * units[b]
            for b, s in enumerate(scenarios)
        ]
        traces = None
        if trace:
            traces = [
                ys[b, :, : s.num_flows] * units[b]
                for b, s in enumerate(scenarios)
            ]
        return FlowBatchResult(results, remaining_bytes, traces,
                               hists=[hist[b] for b in range(B)])


def simulate_grid(
    networks: Sequence[str],
    workloads: Sequence[str],
    loads: Sequence[float],
    seeds: Sequence[int] = (0,),
    engine: str = "auto",
    tile_size: int = DEFAULT_TILE,
    window_tiles: int = DEFAULT_WINDOW_TILES,
    chunk_steps: int = DEFAULT_CHUNK_STEPS,
    **kw,
) -> List[Dict]:
    """The full (network x workload x load x seed) grid in ONE batched
    device program (a single vmapped call on the dense engine; a shared
    chunk loop whose every dispatch covers the whole grid on the tiled
    engine).  Returns one flat row per scenario: the grid coordinates
    plus every `FlowSimResult` field — ready for `sweep.summarize`."""
    grid = list(itertools.product(networks, workloads, loads, seeds))
    scenarios = [
        build_scenario(net, w, load, seed=seed, **kw)
        for net, w, load, seed in grid
    ]
    batch = simulate_flows_batch(
        scenarios, engine=engine, tile_size=tile_size,
        window_tiles=window_tiles, chunk_steps=chunk_steps,
    )
    rows = []
    for (net, w, load, seed), r in zip(grid, batch.results):
        row = dict(network=net, workload=w, load=float(load), seed=int(seed))
        row.update(
            (f.name, getattr(r, f.name))
            for f in r.__dataclass_fields__.values()
        )
        rows.append(row)
    return rows


def saturation_ladder(
    network: str,
    workload: str,
    loads: Sequence[float],
    seeds: Sequence[int] = (0,),
    engine: str = "auto",
    **kw,
) -> List[Dict]:
    """A full load ladder (loads x seeds) to the admission knee in one
    batched device program; one row per load with the seed-majority
    admission verdict.  `flows.saturation_load` stacks two of these
    into a batched bisection.  Rows are grouped positionally by grid
    index (the grid is loads-major over seeds), so repeated or
    float-unstable load values can never merge or drop rows."""
    rows = simulate_grid([network], [workload], loads, seeds=seeds,
                         engine=engine, **kw)
    n_seeds = len(seeds)
    if len(rows) != len(loads) * n_seeds:
        raise RuntimeError(
            f"ladder grid returned {len(rows)} rows for "
            f"{len(loads)} loads x {n_seeds} seeds"
        )
    out = []
    for i, load in enumerate(loads):
        mine = rows[i * n_seeds:(i + 1) * n_seeds]
        out.append(
            dict(
                load=float(load),
                admitted_frac=float(np.mean([r["admitted"] for r in mine])),
                backlog_frac=float(np.mean([r["backlog_frac"] for r in mine])),
                finished_frac=float(np.mean([r["finished_frac"] for r in mine])),
            )
        )
    return out
