"""Slice-stepped fluid simulator for rotor fabrics + static comparisons.

Bulk traffic in Opera/RotorNet is fundamentally fluid at the slice
timescale: buffers drain over direct circuits (plus RotorLB's two-hop
relay when capacity is spare and demand is skewed).  This engine steps
topology slices, moving bytes over live matchings — faithful to §4.2.2
and sufficient for every bulk-side figure (8, 10, 12) of the paper.

This module is the **numpy reference oracle**.  The per-slice recurrence
(`rotor_slice_step`) is a deterministic, fully-vectorized function of
the dense slice adjacency exported by `OperaTopology.matching_tensor`;
the batched jnp engine in `netsim/fluid_jax.py` implements *identical*
math (lockstep-tested by tests/test_netsim_jax.py; the SC-AST-LOCKSTEP
staticcheck rule flags diffs touching one file without the other) and
is the one the benchmark sweeps run on.  That engine now carries two
interchangeable backends — the dense scan mirroring this oracle
term-for-term, and a permutation-sparse gather/scatter form
(`kernels/rotor_slice/`, fed by `OperaTopology.
matching_index_tensor()`) that reaches the k >= 32 Appendix-B design
points — but *this* dense numpy recurrence stays the single source of
truth both parity-test against.  RotorLB's VLB spreading is modeled as a
proportional fluid allocation: each rack offers its queued backlog to
all live partners in proportion to their spare circuit room (rather
than the earlier greedy top-4 heuristic), which is both closer to a
fluid limit of RotorLB's per-slot offers and expressible as one
matmul — the property that lets the jnp engine scan it.

Static networks are served by a max-min fluid share over their fixed
graphs (expander) or their oversubscription bottleneck (folded Clos).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.configs.opera_paper import OperaNetConfig
from repro.core.schedule import cycle_timing, slice_capacity_bytes
from repro.core.topology import OperaTopology, build_opera_topology


@dataclasses.dataclass
class RotorFluidResult:
    finished_frac: List[float]          # per slice-step, fraction of bytes done
    time_us: List[float]
    fct_99_ms: float
    fct_mean_ms: float
    throughput_gbps: float              # aggregate goodput
    wire_bytes: float                   # total bytes that crossed links
    goodput_bytes: float                # demand bytes delivered
    slices_run: int
    blackholed_bytes: float = 0.0       # sent into undetected-dead circuits

    @property
    def bandwidth_tax(self) -> float:
        return self.wire_bytes / max(self.goodput_bytes, 1.0) - 1.0


def rotor_slice_step(
    own: np.ndarray,
    relay: np.ndarray,
    adj_cap: np.ndarray,
    vlb: bool = True,
) -> Tuple[np.ndarray, np.ndarray, float, float]:
    """One topology slice of the rotor fluid recurrence.

    `adj_cap[i, j]` is the byte budget of the i-j circuit this slice
    (0 where dark).  Three phases, each a dense array op:

      1. direct drain: own traffic for the connected partner;
      2. relay drain: 2-hop traffic now one hop from its destination;
      3. RotorLB VLB: leftover circuit room carries queued own traffic
         to the partner as relay (the taxed first hop), source backlog
         offered proportionally and partner room filled proportionally —
         ``relay += (room / row_room).T @ take`` in one matmul.

    This function is the semantic contract for the batched jnp engine
    (`fluid_jax._slice_step` implements the same math); change the two
    together.  Returns (own, relay, delivered_bytes, vlb_first_hop_bytes).
    """
    send_own = np.minimum(own, adj_cap)
    own = own - send_own
    room = adj_cap - send_own
    send_relay = np.minimum(relay, room)
    relay = relay - send_relay
    room = room - send_relay
    delivered = float(send_own.sum() + send_relay.sum())

    moved = 0.0
    if vlb:
        # backlog eligible for spreading: not deliverable directly this
        # slice (a live pair's residual would arrive anyway, and relaying
        # it to its own destination would strand bytes on the diagonal)
        elig = np.where(adj_cap > 0, 0.0, own)
        q = elig.sum(1)                       # spreadable backlog per rack
        r = room.sum(1)                       # spare circuit room per rack
        t = np.minimum(q, r)                  # bytes rack s may spread
        take = elig * np.divide(t, q, out=np.zeros_like(q), where=q > 0)[:, None]
        share = room * np.divide(
            np.ones_like(r), r, out=np.zeros_like(r), where=r > 0
        )[:, None]                            # partner share of s's spread
        own = own - take
        relay = relay + share.T @ take
        moved = float(t.sum())                # first hop of the 2-hop path
    return own, relay, delivered, moved


def rotor_slice_step_faulted(
    own: np.ndarray,
    relay: np.ndarray,
    adj_cap: np.ndarray,
    e_real: np.ndarray,
    e_known: np.ndarray,
    tor_real: np.ndarray,
    tor_known: np.ndarray,
    pair_dead: np.ndarray,
    vlb: bool = True,
) -> Tuple[np.ndarray, np.ndarray, float, float, float]:
    """`rotor_slice_step` under failure masks (from `faults.step_masks`).

    Graceful-degradation semantics (§3.4, Fig. 11):

      * offered capacity excludes *detected*-dead edges and physically
        dead source ToRs: ``cap = adj * (1 - e_known) * (1 - tor_real)``
        on the row side — direct traffic re-queues around known holes;
      * bytes committed to an edge that is dead but not yet detected
        (the hello-protocol lag) consume the wire slot and are lost in
        flight: they stay queued at the source (retransmit) and count
        toward ``blackholed``;
      * VLB spreads only backlog for destinations not known-dead, over
        believed-live room; the blackholed fraction of the spread is
        refunded to the source queue;
      * relayed bytes whose direct circuit to the destination is known
        dead for the whole cycle (``pair_dead``, one serving switch per
        pair) re-join the spread — RotorLB forwards non-local traffic
        onward rather than hold it for a circuit that will not come.

    With all-zero masks every expression reduces to the exact
    failure-free arithmetic (x*1.0 and x+0.0 are IEEE-exact), so
    `FailureSchedule.empty()` is bit-identical to `rotor_slice_step`.
    `fluid_jax._slice_step` given faults implements the same math in
    jnp — change the two together.  Returns (own, relay, delivered_bytes,
    vlb_first_hop_bytes, blackholed_bytes).
    """
    cap = adj_cap * (1.0 - e_known) * (1.0 - tor_real)[:, None]
    arrive = 1.0 - e_real
    send_own = np.minimum(own, cap)
    own = own - send_own * arrive
    room = cap - send_own
    send_relay = np.minimum(relay, room)
    relay = relay - send_relay * arrive
    room = room - send_relay
    delivered = float((send_own * arrive).sum() + (send_relay * arrive).sum())
    # summed directly, not as attempted - delivered: a difference of two
    # large sums keeps their rounding error and can go negative
    blackholed = float((send_own * e_real).sum() + (send_relay * e_real).sum())

    moved = 0.0
    if vlb:
        dst_ok = 1.0 - tor_known
        elig = np.where(cap > 0, 0.0, own * dst_ok[None, :])
        relig = relay * pair_dead * dst_ok[None, :]   # stuck relay re-spreads
        q = elig.sum(1) + relig.sum(1)
        r = room.sum(1)
        t = np.minimum(q, r)
        frac = np.divide(t, q, out=np.zeros_like(q), where=q > 0)[:, None]
        take = elig * frac
        rtake = relig * frac
        share = room * np.divide(
            np.ones_like(r), r, out=np.zeros_like(r), where=r > 0
        )[:, None]
        lost = (share * e_real).sum(1)        # spread fraction that blackholes
        own = own - take + take * lost[:, None]
        relay = relay - rtake + rtake * lost[:, None]
        relay = relay + (share * arrive).T @ (take + rtake)
        lost_bytes = float(((take + rtake).sum(1) * lost).sum())
        moved = float(t.sum()) - lost_bytes   # first hops that truly crossed
        blackholed += lost_bytes
    return own, relay, delivered, moved, blackholed


def simulate_rotor_bulk(
    cfg: OperaNetConfig,
    demand: np.ndarray,            # rack->rack bytes (bulk class)
    vlb: bool = True,
    max_cycles: int = 400,
    topo: Optional[OperaTopology] = None,
    seed: int = 0,
    faults=None,                   # Optional[faults.FailureSchedule]
    paced_cycles: int = 0,
) -> RotorFluidResult:
    n = cfg.num_racks
    topo = topo or build_opera_topology(n, cfg.u, seed=seed, groups=cfg.groups)
    t = cycle_timing(cfg)
    cap = slice_capacity_bytes(cfg, t)       # bytes/link/slice
    adj_caps = topo.matching_tensor().astype(np.float64) * cap

    masks = None
    if faults is not None and faults.events:
        # Event-less schedules skip mask compilation and run the
        # original failure-free step — mirrors `fluid_jax`'s dispatch,
        # which keeps `FailureSchedule.empty()` bit-identical there.
        from repro.netsim.faults import compile_fault_masks, step_masks

        masks = compile_fault_masks(topo, faults)

    own = demand.astype(np.float64).copy()
    total = own.sum()
    inject = None
    if paced_cycles:
        # paced offering: demand arrives in equal installments at the
        # first `paced_cycles` cycle starts instead of all at t=0
        inject = own * (1.0 / paced_cycles)
        own = np.zeros_like(own)
    relay = np.zeros_like(own)
    done = 0.0
    wire = 0.0
    blackholed = 0.0
    finished, times = [], []

    steps = 0
    for step in range(max_cycles * topo.num_slices):
        sl = step % topo.num_slices
        if inject is not None and sl == 0 and step // topo.num_slices < paced_cycles:
            own = own + inject
        if masks is None:
            own, relay, delivered, moved = rotor_slice_step(
                own, relay, adj_caps[sl], vlb
            )
        else:
            e_real, e_known, tor_real, tor_known, pair_dead = step_masks(
                masks, 0, step, sl)
            own, relay, delivered, moved, blk = rotor_slice_step_faulted(
                own, relay, adj_caps[sl],
                e_real, e_known, tor_real, tor_known, pair_dead, vlb,
            )
            blackholed += blk
        done += delivered
        wire += delivered + moved
        steps += 1
        finished.append(done / max(total, 1.0))
        times.append((step + 1) * t.slice_us)
        if done >= total * 0.99999:
            break

    arr = np.array(finished)
    tms = np.array(times) / 1e3
    fct99 = float(tms[np.searchsorted(arr, 0.99)]) if arr[-1] >= 0.99 else float("inf")
    fct_mean = float(np.interp(0.5, arr, tms))
    dur_s = times[-1] * 1e-6
    return RotorFluidResult(
        finished_frac=finished,
        time_us=times,
        fct_99_ms=fct99,
        fct_mean_ms=fct_mean,
        throughput_gbps=done * 8 / dur_s / 1e9,
        wire_bytes=wire,
        goodput_bytes=done,
        slices_run=steps,
        blackholed_bytes=blackholed,
    )


# ---------------- static comparison networks --------------------------------


@dataclasses.dataclass
class StaticFluidResult:
    fct_99_ms: float
    throughput_gbps: float
    wire_bytes: float
    goodput_bytes: float

    @property
    def bandwidth_tax(self) -> float:
        return self.wire_bytes / max(self.goodput_bytes, 1.0) - 1.0


def simulate_expander_bulk(
    adj: np.ndarray,
    demand: np.ndarray,
    link_rate_gbps: float,
    dt_us: float = 100.0,
    max_steps: int = 1_000_000,
) -> StaticFluidResult:
    """Max-min fluid over a static expander with shortest-path routing.

    Every byte consumes `hops` link-slots (the bandwidth tax); service is
    a per-source fair share of each link.  We approximate max-min by
    uniform sharing over the flows crossing each link, iterated per step.
    """
    from repro.core.routing import bfs_next_hop

    n = adj.shape[0]
    dist, nxt = bfs_next_hop(adj)
    # link loads: route demand along shortest paths, precompute per-pair path
    paths: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
    for s in range(n):
        for d in range(n):
            if s == d or demand[s, d] <= 0:
                continue
            path = []
            cur = s
            while cur != d:
                h = nxt[cur, d]
                if h < 0:
                    break
                path.append((cur, h))
                cur = h
            paths[(s, d)] = path

    left = demand.astype(np.float64).copy()
    cap_per_step = link_rate_gbps * 1e9 / 8 * dt_us * 1e-6
    total = left.sum()
    done, wire, steps = 0.0, 0.0, 0
    done_hist, t_hist = [], []
    active = {k for k, v in paths.items() if left[k] > 0}
    while active and steps < max_steps:
        # count flows per link
        link_flows: Dict[Tuple[int, int], int] = {}
        for k in active:
            for e in paths[k]:
                link_flows[e] = link_flows.get(e, 0) + 1
        newly_done = []
        for k in active:
            share = min(cap_per_step / link_flows[e] for e in paths[k])
            mv = min(left[k], share)
            left[k] -= mv
            done += mv
            wire += mv * len(paths[k])
            if left[k] <= 0:
                newly_done.append(k)
        for k in newly_done:
            active.remove(k)
        steps += 1
        done_hist.append(done / max(total, 1.0))
        t_hist.append(steps * dt_us / 1e3)
        if done >= total * 0.99999:
            break
    arr = np.array(done_hist)
    fct99 = float(np.array(t_hist)[np.searchsorted(arr, 0.99)]) if arr[-1] >= 0.99 else float("inf")
    dur_s = steps * dt_us * 1e-6
    return StaticFluidResult(
        fct_99_ms=fct99,
        throughput_gbps=done * 8 / dur_s / 1e9,
        wire_bytes=wire,
        goodput_bytes=done,
    )


def simulate_clos_bulk(
    num_hosts: int,
    demand: np.ndarray,          # rack-level
    link_rate_gbps: float,
    oversubscription: float = 3.0,
) -> StaticFluidResult:
    """Folded Clos as its two binding constraints: per-host NIC rate and
    the core bottleneck (aggregate inter-rack capacity = hosts*rate/M)."""
    total = demand.sum()
    core_gbps = num_hosts * link_rate_gbps / oversubscription
    # per-rack egress also bounded by d*rate
    num_racks = demand.shape[0]
    hosts_per_rack = num_hosts // num_racks
    rack_out = demand.sum(1).max()
    rack_in = demand.sum(0).max()
    egress_gbps = hosts_per_rack * link_rate_gbps
    t_core = total * 8 / (core_gbps * 1e9)
    t_edge = max(rack_out, rack_in) * 8 / (egress_gbps * 1e9)
    dur = max(t_core, t_edge, 1e-9)
    return StaticFluidResult(
        fct_99_ms=dur * 1e3,
        throughput_gbps=total * 8 / dur / 1e9,
        wire_bytes=total,  # direct routing: no tax
        goodput_bytes=total,
    )
