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

Faults: each engine is one jitted program (`_run_batch`,
`_run_batch_sparse`) whose fault operand is an optional `RotorFaults`
pytree.  ``None`` is resolved at trace time, so the failure-free
lowering carries no mask work at all; given one, the scan carries the
global step counter and blackholed total, rebuilds the per-step masks
from the timelines, and may pace the demand.  jit keys its cache on the
operand's pytree structure, so every failure draw shares one faulted
lowering.  The faulted sparse step has no kernel: it runs
`ref.rotor_slice_faulted_ref`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.opera_paper import OperaNetConfig
from repro.core.schedule import cycle_timing, slice_capacity_bytes
from repro.core.topology import OperaTopology, build_opera_topology
from repro.netsim.fluid import RotorFluidResult


_TIMELINES = ("up_onset", "up_detect", "up_recover",
              "tor_onset", "tor_detect", "tor_recover")


class RotorFaults(NamedTuple):
    """The faulted programs' extra operand: a `faults.FaultMasks` on the
    device.  The six component timelines carry a leading batch axis, one
    failure draw per row; ``pair_sw`` (N, N) — the one switch serving
    each rack pair — and, for the dense engine, ``switch_id`` (S, N, N)
    are shared design-time maps."""

    pair_sw: jax.Array
    up_onset: jax.Array      # (B, N, S+1)
    up_detect: jax.Array
    up_recover: jax.Array
    tor_onset: jax.Array     # (B, N)
    tor_detect: jax.Array
    tor_recover: jax.Array
    switch_id: Optional[jax.Array] = None   # dense engine only

    @classmethod
    def from_masks(cls, masks, dense: bool) -> "RotorFaults":
        return cls(
            jnp.asarray(masks.pair_switch),
            *(jnp.asarray(getattr(masks, f)) for f in _TIMELINES),
            switch_id=jnp.asarray(masks.switch_id) if dense else None,
        )


# vmap axes of a `RotorFaults`: timelines per row, maps shared
_ROW_AXES = RotorFaults(None, *(0,) * len(_TIMELINES), switch_id=None)


def _slice_masks(g, sw, faults, dtype):
    """Per-step failure masks of one scenario at global step `g`, from
    its component timelines (int32 comparisons, `faults.step_masks` is
    the numpy reference): (e_real, e_known, pair_dead, tor_real,
    tor_known)."""
    up_f = (g >= faults.up_onset) & (g < faults.up_recover)
    up_k = (g >= faults.up_detect) & (g < faults.up_recover)
    tor_fb = (g >= faults.tor_onset) & (g < faults.tor_recover)
    tor_kb = (g >= faults.tor_detect) & (g < faults.tor_recover)
    i_f = jnp.take_along_axis(up_f, sw, axis=1)
    i_k = jnp.take_along_axis(up_k, sw, axis=1)
    e_real = (i_f | i_f.T | tor_fb[:, None] | tor_fb[None, :]).astype(dtype)
    e_known = (i_k | i_k.T | tor_kb[:, None] | tor_kb[None, :]).astype(dtype)
    p_k = jnp.take_along_axis(up_k, faults.pair_sw, axis=1)
    pair_dead = (
        p_k | p_k.T | tor_kb[:, None] | tor_kb[None, :]
    ).astype(dtype)
    return e_real, e_known, pair_dead, tor_fb.astype(dtype), tor_kb.astype(dtype)


def _slice_step(state, xs, vlb: bool, faults=None):
    """One topology slice, pure jnp — the dense scan body.

    Mirrors `fluid.rotor_slice_step` exactly (normalized units: every
    live edge's capacity is 1.0); change the two together.  Given
    `faults` (one row of a `RotorFaults`) it mirrors
    `fluid.rotor_slice_step_faulted` instead: ``state`` then also carries
    the blackholed total and the global step counter, ``xs`` is
    (adjacency, switch ids), and the masks are rebuilt from the counter
    each step — masks are data, so one lowering serves every failure
    draw.  Under an empty schedule the faulted expressions reduce to the
    clean ones (x*1.0, x+0.0) but XLA may order reductions differently,
    a last-ulp drift: the public API runs event-less schedules clean
    (`_faults_all_empty`).
    """
    if faults is None:
        own, relay, done, wire = state
        cap = xs
    else:
        own, relay, done, wire, blk, g = state
        adj, sw = xs
        e_real, e_known, pair_dead, tor_real, tor_known = _slice_masks(
            g, sw, faults, own.dtype)
        cap = adj * (1.0 - e_known) * (1.0 - tor_real)[:, None]
        arrive = 1.0 - e_real

    def landed(x):   # what a send delivers: all of it unless faulted
        return x if faults is None else x * arrive

    send_own = jnp.minimum(own, cap)
    own = own - landed(send_own)
    room = cap - send_own
    send_relay = jnp.minimum(relay, room)
    relay = relay - landed(send_relay)
    room = room - send_relay
    delivered = landed(send_own).sum() + landed(send_relay).sum()
    done = done + delivered
    wire = wire + delivered
    if faults is not None:
        # lost sends summed directly (see fluid.rotor_slice_step_faulted)
        blk = blk + (send_own * e_real).sum() + (send_relay * e_real).sum()
    if vlb:
        if faults is None:
            elig = jnp.where(cap > 0, 0.0, own)
            q = elig.sum(1)
        else:
            dst_ok = 1.0 - tor_known
            elig = jnp.where(cap > 0, 0.0, own * dst_ok[None, :])
            relig = relay * pair_dead * dst_ok[None, :]  # stuck relay re-spreads
            q = elig.sum(1) + relig.sum(1)
        r = room.sum(1)
        t = jnp.minimum(q, r)
        frac = jnp.where(q > 0, t / jnp.maximum(q, 1e-30), 0.0)[:, None]
        take = elig * frac
        if faults is not None:
            rtake = relig * frac
        share = room * jnp.where(r > 0, 1.0 / jnp.maximum(r, 1e-30), 0.0)[:, None]
        # HIGHEST: the TPU's default f32 matmul rounds through bf16
        hi = jax.lax.Precision.HIGHEST
        if faults is None:
            own = own - take
            relay = relay + jnp.matmul(share.T, take, precision=hi)
            wire = wire + t.sum()
        else:
            lost = (share * e_real).sum(1)
            own = own - take + take * lost[:, None]
            relay = relay - rtake + rtake * lost[:, None]
            relay = relay + jnp.matmul((share * arrive).T, take + rtake,
                                       precision=hi)
            lost_sum = ((take + rtake).sum(1) * lost).sum()
            wire = wire + (t.sum() - lost_sum)
            blk = blk + lost_sum
    if faults is None:
        return (own, relay, done, wire), (done, wire)
    return (own, relay, done, wire, blk, g + 1), (done, wire)


@functools.partial(
    jax.jit, static_argnames=("vlb", "num_cycles", "paced_cycles")
)
def _run_batch(adj, own0, vlb: bool, num_cycles: int,
               faults: Optional[RotorFaults] = None, paced_cycles: int = 0):
    """vmap(scan(scan)): batch -> cycles -> slices.  Returns cumulative
    delivered/wire trajectories (B, num_cycles*num_slices) and the final
    undelivered residual (B,), all in normalized units.

    `faults` runs every row under its own failure draw and appends the
    per-row blackholed total to the outputs.  `paced_cycles`, which
    needs `faults`, injects each row's demand in equal parts at the
    first that many cycle starts instead of all at t=0.  The topology
    tensor and the fault maps are shared design-time state."""

    def one_scenario(own_init, f):
        step = functools.partial(_slice_step, vlb=vlb)
        xs = adj
        own_start = own_init
        if f is not None:
            step = functools.partial(step, faults=f)
            xs = (adj, f.switch_id)
            if paced_cycles:
                inject = own_init * (1.0 / paced_cycles)
                own_start = jnp.zeros_like(own_init)

        def one_cycle(carry, c):
            if paced_cycles:
                own = carry[0] + inject * (c < paced_cycles).astype(own_start.dtype)
                carry = (own, *carry[1:])
            carry, ys = jax.lax.scan(step, carry, xs)
            return carry, ys

        carry0 = (
            own_start,
            jnp.zeros_like(own_start),
            jnp.zeros((), own_start.dtype),
            jnp.zeros((), own_start.dtype),
        )
        if f is not None:
            carry0 += (jnp.zeros((), own_start.dtype), jnp.zeros((), jnp.int32))
        cycles = None if f is None else jnp.arange(num_cycles, dtype=jnp.int32)
        (own, relay, *rest), (done_t, wire_t) = jax.lax.scan(
            one_cycle, carry0, cycles, length=num_cycles)
        out = done_t.reshape(-1), wire_t.reshape(-1), own.sum() + relay.sum()
        return out if f is None else out + (rest[2],)

    row_axes = None if faults is None else _ROW_AXES
    return jax.vmap(one_scenario, in_axes=(0, row_axes))(own0, faults)


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


@functools.partial(jax.jit, static_argnames=("vlb",))
def _sparse_slice_step_faulted(own, relay, done, wire, blk, g, dst,
                               faults: RotorFaults, vlb: bool):
    """Faulted sparse step — the scan body of `_run_batch_sparse` under
    faults: rebuild the per-step masks from the compiled component
    timelines (same int32 comparisons as `_slice_masks`, so masks stay
    *data* and one lowering serves every failure draw), then run the
    edge-layout faulted math (`ref.rotor_slice_faulted_ref`; there is no
    faulted kernel).  Slot s of ``dst`` is switch s, so the per-uplink
    timelines apply directly by slot; only the pair-dead relay mask
    still needs the dense ``pair_sw`` serving-switch gather."""
    from repro.kernels.rotor_slice.ref import rotor_slice_faulted_ref

    bsz, n = own.shape[0], own.shape[1]
    u = dst.shape[1]
    up_f = (g >= faults.up_onset) & (g < faults.up_recover)
    up_k = (g >= faults.up_detect) & (g < faults.up_recover)
    tor_fb = (g >= faults.tor_onset) & (g < faults.tor_recover)
    tor_kb = (g >= faults.tor_detect) & (g < faults.tor_recover)
    psw = jnp.broadcast_to(faults.pair_sw[None], (bsz, n, n))
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


def _trajectories(ys):
    """(num_cycles, num_slices, B) scan outputs -> (B, num_cycles*num_slices)."""
    return tuple(y.reshape(-1, y.shape[-1]).T for y in ys)


@functools.partial(
    jax.jit, static_argnames=("vlb", "num_cycles", "paced_cycles")
)
def _run_batch_sparse(dst, own0, vlb: bool, num_cycles: int,
                      faults: Optional[RotorFaults] = None,
                      paced_cycles: int = 0):
    """Sparse analogue of `_run_batch`: same outputs, faults and pacing,
    one program per call.  The batch rides the kernel's grid instead of
    a vmap: scan over cycles, inner scan over the ``(S, N, u)`` index
    tensor, `_sparse_slice_step` as the body (`_sparse_slice_step_faulted`
    under faults, with the global step counter and blackholed total in
    the carry)."""
    bsz = own0.shape[0]
    if faults is None:
        def step(carry, d):
            carry = _sparse_slice_step(*carry, d, vlb)
            return carry, carry[2:]

        carry0 = (own0, jnp.zeros_like(own0),
                  jnp.zeros((bsz,), own0.dtype), jnp.zeros((bsz,), own0.dtype))
    else:
        own_start = own0
        if paced_cycles:
            inject = own0 * (1.0 / paced_cycles)
            own_start = jnp.zeros_like(own0)

        def step(carry, d):
            carry = _sparse_slice_step_faulted(*carry, d, faults, vlb)
            return carry, carry[2:4]

        zero = jnp.zeros((bsz,), own0.dtype)
        carry0 = (own_start, jnp.zeros_like(own0), zero, zero, zero,
                  jnp.zeros((), jnp.int32))

    def one_cycle(carry, c):
        if paced_cycles:
            own = carry[0] + jnp.where(c < paced_cycles, inject, 0.0)
            carry = (own, *carry[1:])
        return jax.lax.scan(step, carry, dst)

    cycles = None if faults is None else jnp.arange(num_cycles, dtype=jnp.int32)
    (own, relay, *rest), ys = jax.lax.scan(
        one_cycle, carry0, cycles, length=num_cycles)
    done_t, wire_t = _trajectories(ys)
    out = done_t, wire_t, own.sum((1, 2)) + relay.sum((1, 2))
    return out if faults is None else out + (rest[2],)


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
    Fig. 11 throughput-retention columns measure.  Either passes the
    engine's program a `RotorFaults` operand (one faulted lowering per
    design point); when neither is set the program runs without it, as
    its failure-free lowering.

    `engine` selects the dense scan or the permutation-sparse gather
    engine (see module docstring); "auto" picks by rack count.  Within
    either engine an event-less `faults` with no pacing runs
    failure-free, so `FailureSchedule.empty()` stays bit-identical to
    the failure-free run.
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
        fault_ops = None
        if not (_faults_all_empty(faults) and not paced_cycles):
            from repro.netsim.faults import (
                FailureSchedule,
                FaultMasks,
                compile_fault_masks,
            )

            if faults is None:
                faults = FailureSchedule.empty(topo)
            masks = (faults if isinstance(faults, FaultMasks)
                     else compile_fault_masks(topo, faults))
            fault_ops = RotorFaults.from_masks(
                masks.broadcast_to(demands.shape[0]), dense=engine == "dense")
        obs.count("fluid.h2d_bytes", sum(
            x.nbytes for x in jax.tree.leaves((own0, sched, fault_ops))))
        obs.count("fluid.scenario_slices",
                  demands.shape[0] * int(max_cycles) * sched.shape[0])

    with obs.span("fluid.run"):
        args = (sched, own0, bool(vlb), int(max_cycles), fault_ops,
                int(paced_cycles))
        if engine == "sparse":
            with obs.span("fluid.sparse.loop"):
                out = _run_batch_sparse(*args)
        else:
            out = _run_batch(*args)

    with obs.span("fluid.readback"):
        # Device f32 trajectories are de-normalized on the host at float64
        # before stats, mirroring the numpy oracle's precision.
        out = [np.asarray(x, np.float64) * cap for x in out]  # staticcheck: ok SC-AST-F64 (host staging)
        done, wire, residual = out[:3]
        blackholed = out[3] if fault_ops is not None else None

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
