"""Pure-jnp oracle for the permutation-sparse rotor slice step.

One Opera slice moves bytes over a union of involutive matchings: the
``(N, u)`` int32 index tensor ``dst`` (`OperaTopology.
matching_index_tensor()` slice) holds each rack's destination per
switch slot, with the sentinel ``N`` marking dark slots (switch
reconfiguring, or a matching's self-loop).  The step is the same math
as the dense `fluid_jax._slice_step` — send own bytes on direct
circuits, forward relayed bytes into leftover room, then VLB-spread
ineligible bytes — but every per-edge quantity lives in ``(B, N, u)``
edge layout instead of ``(B, N, N)`` masks, so the arithmetic is
O(B·N·(N+u)) instead of the dense engine's O(B·N²·u) relay matmul.

Two structural tricks keep it scatter-free (XLA CPU scatters serialize):

* ``_apply_edges`` realises ``dense[b, i, dst[i, s]] += vals[b, i, s]``
  as u fused compare-selects against an iota — the sentinel never
  matches, so dark slots drop out with no clamping epsilon.
* the relay scatter ``relay[dst[j, s], :] += ...`` becomes a gather,
  because matchings are involutions: ``dst[dst[j, s], s] == j``.

`kernels/rotor_slice/kernel.py` is the Pallas form of this exact math
and `ops.py` parity-gates the two; `fluid_jax._sparse_slice_step`,
the scan body of the sparse engine's slice loop, drives it and
`fluid.rotor_slice_step` (numpy, f64) stays the engine-level oracle.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def apply_edges(dense: jnp.ndarray, dst: jnp.ndarray,
                vals: jnp.ndarray) -> jnp.ndarray:
    """``dense[b, i, dst[i, s]] += vals[b, i, s]`` without a scatter.

    One fused compare-select per switch slot: ``dst[:, s]`` broadcast
    against a column iota marks each slot's live edges.  Sentinel rows
    (``dst == N``) never match the iota, so invalid slots contribute
    exactly 0.0 — no index clamping, no masking epsilon.

    The selects nest into a single accumulator that is added to
    ``dense`` once at the end, not once per slot.  This REQUIRES the
    Opera slice property that slots are disjoint — each (i, j) pair is
    served by at most one switch per slice — so at most one select fires
    per element and nesting is exactly the sum (later slots pass
    non-hits through).  Bitwise-identical to the add-per-slot form
    (adding the skipped slots' 0.0 was a no-op), but u-1 fewer full
    (B, N, N) add passes — measured ~15% off the whole sparse step at
    N = 432 on XLA CPU.
    """
    n = dense.shape[-1]
    iota = jnp.arange(n, dtype=dst.dtype)
    acc = None
    for s in range(dst.shape[1]):
        hit = (dst[:, s:s + 1] == iota[None, :])[None]    # (1, N, N)
        v = vals[:, :, s:s + 1]
        acc = jnp.where(hit, v, 0.0) if acc is None else jnp.where(hit, v, acc)
    return dense + acc


def _slot_sum(e: jnp.ndarray) -> jnp.ndarray:
    """Sum over the slot axis, slot by slot in order — the accumulation
    order of the Pallas kernel's slot loop, so the two agree bitwise."""
    acc = e[..., 0]
    for s in range(1, e.shape[-1]):
        acc = acc + e[..., s]
    return acc


def rack_totals(stats: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B,) delivered and VLB-moved totals from (B, N, 3) per-rack
    columns (sent own, sent relay, moved).  The barrier makes XLA reduce
    the same materialized array whichever path produced it, so the ref
    path and the Pallas kernel agree bitwise."""
    stats = jax.lax.optimization_barrier(stats)
    return (stats[:, :, 0].sum(1) + stats[:, :, 1].sum(1),
            stats[:, :, 2].sum(1))


def rotor_slice_ref(
    own: jnp.ndarray,     # (B, N, N) undelivered source->dst bytes
    relay: jnp.ndarray,   # (B, N, N) relayed bytes awaiting 2nd hop
    dst: jnp.ndarray,     # (N, u) int32, sentinel N = dark slot
    vlb: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One slice step in edge layout; returns (own, relay, delivered,
    moved) with (B,) delivered/VLB-spread totals in normalized units
    (every live edge carries capacity 1.0 for one slice)."""
    bsz, n = own.shape[0], own.shape[1]
    u = dst.shape[1]
    valid = dst < n
    dstc = jnp.where(valid, dst, 0)
    vf = valid.astype(own.dtype)[None]                    # (1, N, u)
    idx = jnp.broadcast_to(dstc[None], (bsz, n, u))

    # direct sends + relay forwarding, all in (B, N, u) edge layout
    own_e = jnp.take_along_axis(own, idx, axis=2) * vf
    send_own_e = jnp.minimum(own_e, vf)
    room_e = vf - send_own_e
    relay_e = jnp.take_along_axis(relay, idx, axis=2) * vf
    send_relay_e = jnp.minimum(relay_e, room_e)
    room_e = room_e - send_relay_e
    so, sr = _slot_sum(send_own_e), _slot_sum(send_relay_e)

    own = apply_edges(own, dst, -send_own_e)
    relay = apply_edges(relay, dst, -send_relay_e)
    if not vlb:
        return (own, relay) + rack_totals(
            jnp.stack([so, sr, jnp.zeros_like(so)], -1))

    # VLB spread.  Eligible bytes are those with no live circuit this
    # slice; subtracting the *pre-send* edge value own_e realises the
    # dense `where(adj > 0, 0, own)` with exact zeros at live edges.
    elig = apply_edges(own, dst, -(own_e - send_own_e))
    q = elig.sum(2)
    r = _slot_sum(room_e)
    t = jnp.minimum(q, r)
    frac = jnp.where(q > 0, t / jnp.maximum(q, 1e-30), 0.0)[:, :, None]
    take = elig * frac
    share_e = room_e * jnp.where(
        r > 0, 1.0 / jnp.maximum(r, 1e-30), 0.0)[:, :, None]
    own = own - take
    # relay[j, :] += sum_s share_e[dst[j, s], s] * take[dst[j, s], :]
    # — the involution turns the scatter into a row gather.
    g_share = jnp.take_along_axis(share_e, idx, axis=1)
    w = vf * g_share
    add = jnp.zeros_like(relay)
    for s in range(u):
        add = add + w[:, :, s:s + 1] * jnp.take(take, dstc[:, s], axis=1)
    relay = relay + add
    return (own, relay) + rack_totals(jnp.stack([so, sr, t], -1))


def rotor_slice_faulted_ref(
    own: jnp.ndarray,       # (B, N, N)
    relay: jnp.ndarray,     # (B, N, N)
    dst: jnp.ndarray,       # (N, u) int32, sentinel N
    up_f: jnp.ndarray,      # (B, N, u) bool — uplink failed (real)
    up_k: jnp.ndarray,      # (B, N, u) bool — uplink failure known
    tor_f: jnp.ndarray,     # (B, N) bool — ToR failed (real)
    tor_k: jnp.ndarray,     # (B, N) bool — ToR failure known
    pair_dead: jnp.ndarray,  # (B, N, N) 0/1 — pair's serving switch dead
    vlb: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Faulted slice step in edge layout — mirrors
    `fluid.rotor_slice_step_faulted` (and the dense
    `fluid_jax._slice_step` given faults); change the three together.
    It is also the faulted sparse engine's step body
    (`fluid_jax._sparse_slice_step_faulted`): no kernel runs under
    faults.

    Slot s of ``dst`` *is* switch s, so the per-uplink masks apply
    directly by slot — no switch-id gather.  An edge is down (really /
    known) when either endpoint's uplink into s is down or either ToR
    is down; the far endpoint's state arrives by the same involution
    gather as the relay spread.  Returns (own, relay, delivered, moved,
    blackholed) with (B,) totals.
    """
    bsz, n = own.shape[0], own.shape[1]
    u = dst.shape[1]
    valid = dst < n
    dstc = jnp.where(valid, dst, 0)
    vf = valid.astype(own.dtype)[None]
    idx = jnp.broadcast_to(dstc[None], (bsz, n, u))

    g_f = jnp.take_along_axis(up_f, idx, axis=1)     # up_f[b, dst[i,s], s]
    g_k = jnp.take_along_axis(up_k, idx, axis=1)
    tor_f_dst = jnp.take_along_axis(
        tor_f, jnp.broadcast_to(dstc[None], (bsz, n, u)).reshape(bsz, -1),
        axis=1).reshape(bsz, n, u)
    tor_k_dst = jnp.take_along_axis(
        tor_k, jnp.broadcast_to(dstc[None], (bsz, n, u)).reshape(bsz, -1),
        axis=1).reshape(bsz, n, u)
    e_real_e = (up_f | g_f | tor_f[:, :, None] | tor_f_dst).astype(own.dtype)
    e_known_e = (up_k | g_k | tor_k[:, :, None] | tor_k_dst).astype(own.dtype)
    tor_real = tor_f.astype(own.dtype)
    tor_known = tor_k.astype(own.dtype)

    cap_e = vf * (1.0 - e_known_e) * (1.0 - tor_real)[:, :, None]
    arrive_e = 1.0 - e_real_e
    own_e = jnp.take_along_axis(own, idx, axis=2) * vf
    send_own_e = jnp.minimum(own_e, cap_e)
    room_e = cap_e - send_own_e
    relay_e = jnp.take_along_axis(relay, idx, axis=2) * vf
    send_relay_e = jnp.minimum(relay_e, room_e)
    room_e = room_e - send_relay_e

    own = apply_edges(own, dst, -send_own_e * arrive_e)
    relay = apply_edges(relay, dst, -send_relay_e * arrive_e)
    delivered = ((send_own_e * arrive_e).sum((1, 2))
                 + (send_relay_e * arrive_e).sum((1, 2)))
    blackholed = ((send_own_e * e_real_e).sum((1, 2))
                  + (send_relay_e * e_real_e).sum((1, 2)))
    if not vlb:
        return own, relay, delivered, jnp.zeros_like(delivered), blackholed

    # Eligibility excludes exactly the edges with usable capacity this
    # slice (cap_e > 0), not merely the live ones: a known-down edge's
    # bytes must VLB-spread.  Zero those edges by subtracting their
    # current values, then weight by destination-ToR health.
    dst_ok = 1.0 - tor_known
    own_after_e = jnp.take_along_axis(own, idx, axis=2)
    capmask_vals = jnp.where(cap_e > 0, own_after_e, 0.0)
    elig = apply_edges(own, dst, -capmask_vals) * dst_ok[:, None, :]
    relig = relay * pair_dead * dst_ok[:, None, :]
    q = elig.sum(2) + relig.sum(2)
    r = room_e.sum(2)
    t = jnp.minimum(q, r)
    frac = jnp.where(q > 0, t / jnp.maximum(q, 1e-30), 0.0)[:, :, None]
    take = elig * frac
    rtake = relig * frac
    share_e = room_e * jnp.where(
        r > 0, 1.0 / jnp.maximum(r, 1e-30), 0.0)[:, :, None]
    lost = (share_e * e_real_e).sum(2)
    own = own - take + take * lost[:, :, None]
    relay = relay - rtake + rtake * lost[:, :, None]
    sa = share_e * arrive_e
    trt = take + rtake
    g_sa = jnp.take_along_axis(sa, idx, axis=1)
    w = vf * g_sa
    add = jnp.zeros_like(relay)
    for s in range(u):
        add = add + w[:, :, s:s + 1] * jnp.take(trt, dstc[:, s], axis=1)
    relay = relay + add
    lost_bytes = (trt.sum(2) * lost).sum(1)
    moved = t.sum(1) - lost_bytes
    blackholed = blackholed + lost_bytes
    return own, relay, delivered, moved, blackholed
