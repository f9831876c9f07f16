"""Batched JAX fluid engine for rotor fabrics.

Re-expresses `fluid.simulate_rotor_bulk` as a jitted `lax.scan` over the
dense ``(num_slices, N, N)`` matching tensor exported at design time by
`OperaTopology.matching_tensor`, with `jax.vmap` over a leading batch
axis of scenarios — the (seed x load-level x workload) grids the paper's
bulk figures sweep.  One compiled call simulates the whole batch; the
per-slice recurrence is numerically identical to the numpy oracle
(`fluid.rotor_slice_step`) and the two are lockstep-tested by
tests/test_netsim_jax.py.

Internals: all byte quantities are normalized to units of one
slice-link capacity (`core.schedule.slice_capacity_bytes`) so float32
keeps ample mantissa headroom, and the topology tensor is a scan
operand — no topology math, python branching, or host sync inside the
step.  The scan runs a fixed ``max_cycles`` budget (scenarios that
finish early just stop moving bytes); completion times are recovered
from the cumulative-delivery trajectory on the host afterwards, exactly
as the oracle's early-exit loop records them.

Two engines share the public API (`engine=` on
`simulate_rotor_bulk_batch`):

* **dense** — the original vmap(scan(scan)) over ``(S, N, N)`` masks.
* **sparse** — gathers over the permutation-sparse
  ``(S, N, u)`` index tensor (`OperaTopology.matching_index_tensor()`,
  sentinel N = dark slot) via the `kernels/rotor_slice` Pallas op,
  cutting the per-slice work from O(N²·u) (the VLB relay matmul) to
  O(N·(N + u)) and the topology artifact from O(S·N²) to O(S·N·u) —
  what makes the k >= 32 Appendix-B points fit on one host.  Like
  dense, it is one jitted program per call: a scan over cycles around
  a scan over the index tensor, the batch riding the kernel's grid.
  On XLA CPU that program is as fast as stepping the same slice
  program from the host, or faster (B = 2, one cycle, median of 6:
  k12-n108 0.08 s -> 0.02 s, k16-n256 0.25 s -> 0.15 s, k24-n432
  0.88-0.91 s -> 0.73-0.88 s), so one path serves every backend.
  ``engine="auto"`` picks sparse at N >= `SPARSE_AUTO_RACKS`, dense
  below.  Both engines agree with the oracle at f32 ulp tolerance
  (tests/test_rotor_slice.py pins sparse-vs-dense on every default
  Appendix-B point, faulted and unfaulted).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.opera_paper import OperaNetConfig
from repro.core.schedule import cycle_timing, slice_capacity_bytes
from repro.core.topology import OperaTopology, build_opera_topology
from repro.netsim.fluid import RotorFluidResult


def _slice_step(state, adj, vlb: bool):
    """One topology slice, pure jnp — the scan body.

    Mirrors `fluid.rotor_slice_step` exactly (normalized units: every
    live edge's capacity is 1.0); change the two together.
    """
    own, relay, done, wire = state
    send_own = jnp.minimum(own, adj)
    own = own - send_own
    room = adj - send_own
    send_relay = jnp.minimum(relay, room)
    relay = relay - send_relay
    room = room - send_relay
    delivered = send_own.sum() + send_relay.sum()
    done = done + delivered
    wire = wire + delivered
    if vlb:
        elig = jnp.where(adj > 0, 0.0, own)
        q = elig.sum(1)
        r = room.sum(1)
        t = jnp.minimum(q, r)
        take = elig * jnp.where(q > 0, t / jnp.maximum(q, 1e-30), 0.0)[:, None]
        share = room * jnp.where(r > 0, 1.0 / jnp.maximum(r, 1e-30), 0.0)[:, None]
        own = own - take
        # HIGHEST: the TPU's default f32 matmul rounds through bf16
        relay = relay + jnp.matmul(share.T, take,
                                   precision=jax.lax.Precision.HIGHEST)
        wire = wire + t.sum()
    return (own, relay, done, wire), (done, wire)


@functools.partial(jax.jit, static_argnames=("vlb", "num_cycles"))
def _run_batch(adj, own0, vlb: bool, num_cycles: int):
    """vmap(scan(scan)): batch -> cycles -> slices.  Returns cumulative
    delivered/wire trajectories (B, num_cycles*num_slices) and the final
    undelivered residual (B,), all in normalized units."""

    def one_scenario(own_init):
        step = functools.partial(_slice_step, vlb=vlb)

        def one_cycle(carry, _):
            carry, ys = jax.lax.scan(step, carry, adj)
            return carry, ys

        carry0 = (
            own_init,
            jnp.zeros_like(own_init),
            jnp.zeros((), own_init.dtype),
            jnp.zeros((), own_init.dtype),
        )
        (own, relay, _, _), (done_t, wire_t) = jax.lax.scan(
            one_cycle, carry0, None, length=num_cycles
        )
        return done_t.reshape(-1), wire_t.reshape(-1), own.sum() + relay.sum()

    return jax.vmap(one_scenario)(own0)


# --------------------------------------------------------------------------
# Permutation-sparse engine (gather/scatter over matching_index_tensor)
# --------------------------------------------------------------------------

# engine="auto" switches to the sparse gather engine at this rack count:
# the dense relay matmul's O(N^2 u) overtakes the sparse step's
# O(N (N + u)) well below this on paper radixes (PERF.md records what
# the chip measures on each side of this threshold).
SPARSE_AUTO_RACKS = 192


@functools.partial(jax.jit, static_argnames=("vlb",))
def _sparse_slice_step(own, relay, done, wire, dst, vlb: bool):
    """One sparse slice step + trajectory accumulation — the scan body
    of `_run_batch_sparse`.  The slice math lives in
    `kernels.rotor_slice` (Pallas; `ref.rotor_slice_ref` is its oracle
    and mirrors `fluid.rotor_slice_step` / `_slice_step`; change them
    together)."""
    from repro.kernels.rotor_slice.ops import rotor_slice_step

    own, relay, delivered, moved = rotor_slice_step(own, relay, dst, vlb=vlb)
    done = done + delivered
    wire = wire + delivered + moved
    return own, relay, done, wire


def _trajectories(ys):
    """(num_cycles, num_slices, B) scan outputs -> (B, num_cycles*num_slices)."""
    return tuple(y.reshape(-1, y.shape[-1]).T for y in ys)


@functools.partial(jax.jit, static_argnames=("vlb", "num_cycles"))
def _run_batch_sparse(dst, own0, vlb: bool, num_cycles: int):
    """Sparse analogue of `_run_batch`: same (done_t, wire_t, residual)
    contract, one program per call.  The batch rides the kernel's grid
    instead of a vmap: scan over cycles, inner scan over the
    ``(S, N, u)`` index tensor, `_sparse_slice_step` as the body."""
    bsz = own0.shape[0]

    def step(carry, d):
        carry = _sparse_slice_step(*carry, d, vlb)
        return carry, carry[2:]

    def one_cycle(carry, _):
        return jax.lax.scan(step, carry, dst)

    carry0 = (
        own0,
        jnp.zeros_like(own0),
        jnp.zeros((bsz,), own0.dtype),
        jnp.zeros((bsz,), own0.dtype),
    )
    (own, relay, _, _), ys = jax.lax.scan(
        one_cycle, carry0, None, length=num_cycles)
    done_t, wire_t = _trajectories(ys)
    return done_t, wire_t, own.sum((1, 2)) + relay.sum((1, 2))


@functools.partial(jax.jit, static_argnames=("vlb",))
def _sparse_slice_step_faulted(
    own, relay, done, wire, blk, g, dst, pair_sw,
    up_onset, up_detect, up_recover, tor_onset, tor_detect, tor_recover,
    vlb: bool,
):
    """Faulted sparse step — the scan body of `_run_batch_sparse_faulted`:
    rebuild the per-step masks from the compiled component timelines
    (same int32 comparisons as `_slice_step_faulted`, so masks stay
    *data* and one lowering serves every failure draw), then run the
    edge-layout faulted math.  Slot s of ``dst`` is switch s, so the
    per-uplink timelines apply directly by slot; only the pair-dead
    relay mask still needs the dense ``pair_sw`` serving-switch gather."""
    from repro.kernels.rotor_slice.ref import rotor_slice_faulted_ref

    bsz, n = own.shape[0], own.shape[1]
    u = dst.shape[1]
    up_f = (g >= up_onset) & (g < up_recover)
    up_k = (g >= up_detect) & (g < up_recover)
    tor_fb = (g >= tor_onset) & (g < tor_recover)
    tor_kb = (g >= tor_detect) & (g < tor_recover)
    psw = jnp.broadcast_to(pair_sw[None], (bsz, n, n))
    p_k = jnp.take_along_axis(up_k, psw, axis=2)
    pair_dead = (
        p_k | jnp.swapaxes(p_k, 1, 2)
        | tor_kb[:, :, None] | tor_kb[:, None, :]
    ).astype(own.dtype)
    own, relay, delivered, moved, blackholed = rotor_slice_faulted_ref(
        own, relay, dst, up_f[:, :, :u], up_k[:, :, :u],
        tor_fb, tor_kb, pair_dead, vlb=vlb)
    done = done + delivered
    wire = wire + delivered + moved
    blk = blk + blackholed
    return own, relay, done, wire, blk, g + 1


@functools.partial(
    jax.jit, static_argnames=("vlb", "num_cycles", "paced_cycles")
)
def _run_batch_sparse_faulted(
    dst, pair_sw, own0,
    up_onset, up_detect, up_recover, tor_onset, tor_detect, tor_recover,
    vlb: bool, num_cycles: int, paced_cycles: int,
):
    """Sparse analogue of `_run_batch_faulted`, one program per call as
    `_run_batch_sparse`, with the global step counter and blackholed
    total in the carry; returns (done_t, wire_t, residual, blackholed)."""
    bsz = own0.shape[0]
    timelines = (up_onset, up_detect, up_recover,
                 tor_onset, tor_detect, tor_recover)
    if paced_cycles:
        inject = own0 * (1.0 / paced_cycles)
        own_start = jnp.zeros_like(own0)
    else:
        own_start = own0

    def step(carry, d):
        carry = _sparse_slice_step_faulted(
            *carry, d, pair_sw, *timelines, vlb)
        return carry, carry[2:4]

    def one_cycle(carry, c):
        if paced_cycles:
            own = carry[0] + jnp.where(c < paced_cycles, inject, 0.0)
            carry = (own, *carry[1:])
        return jax.lax.scan(step, carry, dst)

    zero = jnp.zeros((bsz,), own0.dtype)
    carry0 = (own_start, jnp.zeros_like(own0), zero, zero, zero,
              jnp.zeros((), jnp.int32))
    (own, relay, _, _, blk, _), ys = jax.lax.scan(
        one_cycle, carry0, jnp.arange(num_cycles, dtype=jnp.int32))
    done_t, wire_t = _trajectories(ys)
    return done_t, wire_t, own.sum((1, 2)) + relay.sum((1, 2)), blk


def _slice_step_faulted(state, xs, ops, vlb: bool):
    """One topology slice under failure masks — the faulted scan body.

    Mirrors `fluid.rotor_slice_step_faulted` exactly: per-step masks are
    rebuilt from the compiled component timelines (`faults.step_masks`
    is the numpy reference) with pure int32 comparisons on the global
    step counter carried through the scan — masks are data, so one
    lowering serves every failure draw; change the two together.  With
    an empty schedule every expression reduces algebraically to
    `_slice_step` (x*1.0 / x+0.0), but XLA's fusion-dependent reduction
    order still drifts the last f32 ulp between the two programs — the
    public API dispatches event-less schedules to `_run_batch` so the
    no-op case stays bit-identical (see `_faults_all_empty`).
    """
    own, relay, done, wire, blk, g = state
    adj, sw = xs
    (pair_sw, up_onset, up_detect, up_recover,
     tor_onset, tor_detect, tor_recover) = ops
    up_f = (g >= up_onset) & (g < up_recover)
    up_k = (g >= up_detect) & (g < up_recover)
    tor_fb = (g >= tor_onset) & (g < tor_recover)
    tor_kb = (g >= tor_detect) & (g < tor_recover)
    i_f = jnp.take_along_axis(up_f, sw, axis=1)
    i_k = jnp.take_along_axis(up_k, sw, axis=1)
    e_real = (i_f | i_f.T | tor_fb[:, None] | tor_fb[None, :]).astype(own.dtype)
    e_known = (i_k | i_k.T | tor_kb[:, None] | tor_kb[None, :]).astype(own.dtype)
    p_k = jnp.take_along_axis(up_k, pair_sw, axis=1)
    pair_dead = (
        p_k | p_k.T | tor_kb[:, None] | tor_kb[None, :]
    ).astype(own.dtype)
    tor_real = tor_fb.astype(own.dtype)
    tor_known = tor_kb.astype(own.dtype)

    cap = adj * (1.0 - e_known) * (1.0 - tor_real)[:, None]
    arrive = 1.0 - e_real
    send_own = jnp.minimum(own, cap)
    own = own - send_own * arrive
    room = cap - send_own
    send_relay = jnp.minimum(relay, room)
    relay = relay - send_relay * arrive
    room = room - send_relay
    delivered = (send_own * arrive).sum() + (send_relay * arrive).sum()
    done = done + delivered
    wire = wire + delivered
    # lost sends summed directly (see fluid.rotor_slice_step_faulted)
    blk = blk + (send_own * e_real).sum() + (send_relay * e_real).sum()
    if vlb:
        dst_ok = 1.0 - tor_known
        elig = jnp.where(cap > 0, 0.0, own * dst_ok[None, :])
        relig = relay * pair_dead * dst_ok[None, :]  # stuck relay re-spreads
        q = elig.sum(1) + relig.sum(1)
        r = room.sum(1)
        t = jnp.minimum(q, r)
        frac = jnp.where(q > 0, t / jnp.maximum(q, 1e-30), 0.0)[:, None]
        take = elig * frac
        rtake = relig * frac
        share = room * jnp.where(r > 0, 1.0 / jnp.maximum(r, 1e-30), 0.0)[:, None]
        lost = (share * e_real).sum(1)
        own = own - take + take * lost[:, None]
        relay = relay - rtake + rtake * lost[:, None]
        relay = relay + jnp.matmul((share * arrive).T, take + rtake,
                                   precision=jax.lax.Precision.HIGHEST)
        lost_sum = ((take + rtake).sum(1) * lost).sum()
        wire = wire + (t.sum() - lost_sum)
        blk = blk + lost_sum
    return (own, relay, done, wire, blk, g + 1), (done, wire)


@functools.partial(
    jax.jit, static_argnames=("vlb", "num_cycles", "paced_cycles")
)
def _run_batch_faulted(
    adj, sw, pair_sw, own0,
    up_onset, up_detect, up_recover, tor_onset, tor_detect, tor_recover,
    vlb: bool, num_cycles: int, paced_cycles: int,
):
    """`_run_batch` with per-row failure timelines (and optional paced
    demand injection).  The mask arrays are vmapped scenario operands —
    every batch row carries an independent failure draw — while the
    topology tensor, its switch-id map, and the per-pair serving-switch
    map are shared design-time state.  Also returns the per-row
    blackholed-byte total."""
    def one_scenario(own_init, uo, ud, ur, to, td, tr):
        step = functools.partial(
            _slice_step_faulted, ops=(pair_sw, uo, ud, ur, to, td, tr), vlb=vlb
        )
        if paced_cycles:
            inject = own_init * (1.0 / paced_cycles)
            own_start = jnp.zeros_like(own_init)
        else:
            own_start = own_init

        def one_cycle(carry, c):
            if paced_cycles:
                own, relay, done, wire, blk, g = carry
                own = own + inject * (c < paced_cycles).astype(own.dtype)
                carry = (own, relay, done, wire, blk, g)
            carry, ys = jax.lax.scan(step, carry, (adj, sw))
            return carry, ys

        carry0 = (
            own_start,
            jnp.zeros_like(own_start),
            jnp.zeros((), own_start.dtype),
            jnp.zeros((), own_start.dtype),
            jnp.zeros((), own_start.dtype),
            jnp.zeros((), jnp.int32),
        )
        (own, relay, _, _, blk, _), (done_t, wire_t) = jax.lax.scan(
            one_cycle, carry0, jnp.arange(num_cycles, dtype=jnp.int32)
        )
        return done_t.reshape(-1), wire_t.reshape(-1), own.sum() + relay.sum(), blk

    return jax.vmap(one_scenario)(
        own0, up_onset, up_detect, up_recover,
        tor_onset, tor_detect, tor_recover,
    )


@dataclasses.dataclass
class RotorBatchResult:
    """Per-scenario bulk stats for a batch of B scenarios over T slices.

    Scalars are (B,) arrays; `finished_frac` keeps the full (B, T)
    trajectory (cumulative fraction of demand delivered after each
    slice).  Delivery stats (goodput/wire/throughput/FCT) are read at
    each scenario's completion step `slices_run` — the same truncation
    the numpy oracle's early-exit loop performs."""

    finished_frac: np.ndarray      # (B, T)
    time_us: np.ndarray            # (T,)
    fct_99_ms: np.ndarray          # (B,)
    fct_mean_ms: np.ndarray        # (B,)
    throughput_gbps: np.ndarray    # (B,)
    wire_bytes: np.ndarray         # (B,)
    goodput_bytes: np.ndarray      # (B,)
    residual_bytes: np.ndarray     # (B,) undelivered at scan end
    total_bytes: np.ndarray        # (B,) offered demand
    slices_run: np.ndarray         # (B,)
    blackholed_bytes: Optional[np.ndarray] = None  # (B,) lost-in-flight sends

    @property
    def bandwidth_tax(self) -> np.ndarray:
        return self.wire_bytes / np.maximum(self.goodput_bytes, 1.0) - 1.0

    @property
    def batch_size(self) -> int:
        return self.finished_frac.shape[0]

    def scenario(self, b: int) -> RotorFluidResult:
        """View one batch row as the numpy engine's result type."""
        k = int(self.slices_run[b])
        return RotorFluidResult(
            finished_frac=list(self.finished_frac[b, :k]),
            time_us=list(self.time_us[:k]),
            fct_99_ms=float(self.fct_99_ms[b]),
            fct_mean_ms=float(self.fct_mean_ms[b]),
            throughput_gbps=float(self.throughput_gbps[b]),
            wire_bytes=float(self.wire_bytes[b]),
            goodput_bytes=float(self.goodput_bytes[b]),
            slices_run=k,
            blackholed_bytes=(
                float(self.blackholed_bytes[b])
                if self.blackholed_bytes is not None else 0.0
            ),
        )


def _faults_all_empty(faults) -> bool:
    """True when `faults` carries no failure events at all — None, an
    event-less `FailureSchedule`, or a sequence of event-less ones.
    Empty schedules dispatch to the original failure-free program so
    the no-op case is bit-identical by construction (the faulted
    lowering matches it only to f32 fusion tolerance)."""
    if faults is None:
        return True
    from repro.netsim.faults import FailureSchedule

    if isinstance(faults, FailureSchedule):
        return faults.is_empty
    if isinstance(faults, (list, tuple)):
        return all(
            isinstance(f, FailureSchedule) and f.is_empty for f in faults
        )
    return False


def resolve_engine(engine: str, num_racks: int) -> str:
    """Map ``engine="auto"`` to "dense"/"sparse" by design-point size."""
    if engine == "auto":
        return "sparse" if num_racks >= SPARSE_AUTO_RACKS else "dense"
    if engine not in ("dense", "sparse"):
        raise ValueError(f"engine must be auto|dense|sparse, got {engine!r}")
    return engine


def simulate_rotor_bulk_batch(
    cfg: OperaNetConfig,
    demands: np.ndarray,           # (B, N, N) or (N, N) rack->rack bytes
    vlb: bool = True,
    max_cycles: int = 400,
    topo: Optional[OperaTopology] = None,
    seed: int = 0,
    dtype=jnp.float32,
    faults=None,               # FailureSchedule | Sequence[FailureSchedule]
    paced_cycles: int = 0,
    engine: str = "auto",      # auto | dense | sparse
) -> RotorBatchResult:
    """Simulate a batch of bulk-demand scenarios in one vmapped call.

    All scenarios share one topology (a design point); the batch axis is
    the scenario grid — different workloads, load levels, and demand
    seeds.  Design-point sweeps call this once per point (shapes differ).

    `faults` is a `faults.FailureSchedule` (shared by every row) or a
    sequence of them (one independent draw per row); `paced_cycles`
    spreads each row's demand over that many cycle starts instead of
    offering it all at t=0 — the sustained-load mode the dynamic
    Fig. 11 throughput-retention columns measure.  Both route through
    one faulted lowering per design point; when neither is set the
    original failure-free program runs untouched.

    `engine` selects the dense scan or the permutation-sparse gather
    engine (see module docstring); "auto" picks by rack count.  Within
    either engine an event-less `faults` with no pacing dispatches to
    that engine's unfaulted program, so `FailureSchedule.empty()` stays
    bit-identical to the failure-free run.
    """
    with obs.span("fluid.prepare"):
        demands = np.asarray(demands, np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
        if demands.ndim == 2:
            demands = demands[None]
        n = cfg.num_racks
        if demands.shape[1:] != (n, n):
            raise ValueError(f"demand shape {demands.shape[1:]} != ({n}, {n})")
        topo = topo or build_opera_topology(n, cfg.u, seed=seed, groups=cfg.groups)
        t = cycle_timing(cfg)
        cap = slice_capacity_bytes(cfg, t)
        engine = resolve_engine(engine, n)

        own0 = jnp.asarray(demands / cap, dtype)
        if engine == "sparse":
            sched = jnp.asarray(topo.matching_index_tensor())
        else:
            sched = jnp.asarray(topo.matching_tensor(), dtype)
        clean = _faults_all_empty(faults) and not paced_cycles
        uploads = [own0, sched]
        blackholed = None
        if not clean:
            from repro.netsim.faults import (
                FailureSchedule,
                FaultMasks,
                compile_fault_masks,
            )

            if faults is None:
                faults = FailureSchedule.empty(topo)
            masks = (faults if isinstance(faults, FaultMasks)
                     else compile_fault_masks(topo, faults))
            masks = masks.broadcast_to(demands.shape[0])
            pair_sw = jnp.asarray(masks.pair_switch)
            timelines = [jnp.asarray(getattr(masks, f)) for f in (
                "up_onset", "up_detect", "up_recover",
                "tor_onset", "tor_detect", "tor_recover")]
            uploads += [pair_sw, *timelines]
            if engine == "dense":
                sw = jnp.asarray(masks.switch_id)
                uploads.append(sw)
        obs.count("fluid.h2d_bytes", sum(x.nbytes for x in uploads))
        obs.count("fluid.scenario_slices",
                  demands.shape[0] * int(max_cycles) * sched.shape[0])

    with obs.span("fluid.run"):
        if engine == "sparse":
            with obs.span("fluid.sparse.loop"):
                if clean:
                    done_t, wire_t, residual = _run_batch_sparse(
                        sched, own0, bool(vlb), int(max_cycles))
                else:
                    (done_t, wire_t, residual,
                     blackholed) = _run_batch_sparse_faulted(
                        sched, pair_sw, own0, *timelines,
                        bool(vlb), int(max_cycles), int(paced_cycles),
                    )
        elif clean:
            done_t, wire_t, residual = _run_batch(
                sched, own0, bool(vlb), int(max_cycles))
        else:
            done_t, wire_t, residual, blackholed = _run_batch_faulted(
                sched, sw, pair_sw, own0, *timelines,
                bool(vlb), int(max_cycles), int(paced_cycles),
            )

    with obs.span("fluid.readback"):
        # Device f32 trajectories are de-normalized on the host at float64
        # before stats, mirroring the numpy oracle's precision.
        done = np.asarray(done_t, np.float64) * cap  # staticcheck: ok SC-AST-F64 (host staging)
        wire = np.asarray(wire_t, np.float64) * cap  # staticcheck: ok SC-AST-F64 (host staging)
        residual = np.asarray(residual, np.float64) * cap  # staticcheck: ok SC-AST-F64 (host staging)
        if not clean:
            blackholed = np.asarray(blackholed, np.float64) * cap  # staticcheck: ok SC-AST-F64 (host staging)

    with obs.span("fluid.stats"):
        totals = demands.sum((1, 2))
        B, T = done.shape
        time_us = (np.arange(T) + 1) * t.slice_us
        fct99 = np.empty(B)
        fct_mean = np.empty(B)
        tput = np.empty(B)
        slices_run = np.empty(B, np.int64)
        finished = done / np.maximum(totals, 1.0)[:, None]
        for b in range(B):
            hit = done[b] >= totals[b] * 0.99999
            k = int(np.argmax(hit)) if hit.any() else T - 1
            slices_run[b] = k + 1
            fin = finished[b, : k + 1]
            tms = time_us[: k + 1] / 1e3
            fct99[b] = (
                float(tms[np.searchsorted(fin, 0.99)])
                if fin[-1] >= 0.99
                else float("inf")
            )
            fct_mean[b] = float(np.interp(0.5, fin, tms))
            dur_s = time_us[k] * 1e-6
            tput[b] = done[b, k] * 8 / dur_s / 1e9

        rows = np.arange(B)
        at_end = (slices_run - 1).clip(0, T - 1)
        return RotorBatchResult(
            finished_frac=finished,
            time_us=time_us,
            fct_99_ms=fct99,
            fct_mean_ms=fct_mean,
            throughput_gbps=tput,
            wire_bytes=wire[rows, at_end],
            goodput_bytes=done[rows, at_end],
            residual_bytes=residual,
            total_bytes=totals,
            slices_run=slices_run,
            blackholed_bytes=blackholed,
        )


def simulate_rotor_bulk_jax(
    cfg: OperaNetConfig,
    demand: np.ndarray,
    vlb: bool = True,
    max_cycles: int = 400,
    topo: Optional[OperaTopology] = None,
    seed: int = 0,
    faults=None,
    paced_cycles: int = 0,
    engine: str = "auto",
) -> RotorFluidResult:
    """Drop-in single-scenario API (batch of one) matching
    `fluid.simulate_rotor_bulk`'s signature and result type."""
    r = simulate_rotor_bulk_batch(
        cfg, demand, vlb=vlb, max_cycles=max_cycles, topo=topo, seed=seed,
        faults=faults, paced_cycles=paced_cycles, engine=engine,
    )
    return r.scenario(0)
