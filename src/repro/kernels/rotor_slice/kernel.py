"""Pallas permutation-sparse rotor slice step.

Grid (B,): one scenario per grid cell, its (N, N) `own` / `relay` tiles
in VMEM; the destination-index tensor (`OperaTopology.
matching_index_tensor()` slice, sentinel N for dark slots) enters
transposed as (u, N) and is broadcast to every cell.  The body is the
math of `ref.rotor_slice_ref`, in the forms Mosaic lowers:

* no gathers: slot s's live edges are the (N, N) one-hot mask
  ``hit_s[i, j] = (dst[i, s] == j)``, symmetric because every matching
  is an involution.  The edge value ``own[i, dst[i, s]]`` is a masked
  row sum, exact since it sums one nonzero term.
* the relay row gather ``take[dst[j, s], :]`` is ``hit_s @ take`` with
  no HIGHEST product: `take` is split once into three bf16 parts that
  sum back to it exactly (`_split3`), each gathered by one single-pass
  bf16 product with the bf16 one-hot.  Every output of such a product
  sums one bf16 value times 1.0 and zeros, so it is exact, and so is
  the re-sum.  The share column goes the same way, its three parts side
  by side in one narrow product.
* slots are a `fori_loop`.  The direct loop's per-edge quantities are
  (N, 1) columns rebuilt per slot, so VMEM holds a fixed number of
  (N, N) tiles whatever u is; each slot's leftover `room` column is
  kept in one (N, u) array, so the spread loop rebuilds only `hit_s`.
* per-rack totals leave through a (B, N, 3) block (full trailing dims,
  a legal Mosaic block); `ref.rack_totals` sums them to (B,).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rotor_slice.ref import rack_totals

# The body keeps about two dozen live (N, N) f32 temporaries beside the
# double-buffered state blocks (18.5 MB of VMEM at N = 432 for v5e);
# Mosaic's default 16 MiB scoped limit is raised to that, within the
# 128 MiB a v5e core has.
_VMEM_LIVE_TILES = 32
_VMEM_CAP_BYTES = 100 * 2**20


def _vmem_limit_bytes(n: int) -> int:
    return min(max(16 * 2**20, _VMEM_LIVE_TILES * n * n * 4), _VMEM_CAP_BYTES)


def _split3(x):
    """Three f32 parts of f32 `x`, each exact in bf16, whose sum is `x`
    bit for bit in any order.  Truncation split: `hi` keeps x's top 16
    bits (sign, exponent, 7 mantissa bits), `mid` the same of the
    remainder, and `lo` what is left, at most 8 significant bits.  The
    parts are disjoint bit fields of x with its sign, so every partial
    sum is exact.  Holds where the parts are normal, |x| >= 2**-103, and
    for +0 (-0 sums back to +0)."""
    def trunc(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)
        return jax.lax.bitcast_convert_type(bits & jnp.int32(-65536),
                                            jnp.float32)

    hi = trunc(x)
    rest = x - hi
    mid = trunc(rest)
    return hi, mid, rest - mid


def _dot(onehot, x):
    """One single-pass bf16 product: rows of `x` picked by `onehot`."""
    return jax.lax.dot_general(onehot, x, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(dst_t_ref, own_ref, relay_ref, own_o, relay_o, stats_o, *,
            vlb: bool):
    own0 = own_ref[0]           # (N, N)
    relay0 = relay_ref[0]
    u, n = dst_t_ref.shape
    rows = jax.lax.broadcasted_iota(dst_t_ref.dtype, (n, n), 0)

    def hit_of(s):
        """Slot s's edge mask.  ``rows == dst[:, s]`` as a row is hit_s
        transposed, which is hit_s itself: the matching is an
        involution."""
        return rows == dst_t_ref[pl.ds(s, 1), :]

    slot_col = jax.lax.broadcasted_iota(jnp.int32, (n, u), 1)

    def direct(s, carry):
        # nested selects: slots are disjoint, at most one fires per element;
        # `rooms` (VLB only) collects each slot's room column for `spread`
        d_own, d_relay, d_elig, r, so, sr, *rooms = carry
        hit = hit_of(s)
        vf = hit.astype(own0.dtype).sum(1, keepdims=True)
        own_e = jnp.where(hit, own0, 0.0).sum(1, keepdims=True)
        send_own = jnp.minimum(own_e, vf)
        relay_e = jnp.where(hit, relay0, 0.0).sum(1, keepdims=True)
        send_relay = jnp.minimum(relay_e, vf - send_own)
        room = vf - send_own - send_relay
        return (jnp.where(hit, -send_own, d_own),
                jnp.where(hit, -send_relay, d_relay),
                jnp.where(hit, -(own_e - send_own), d_elig),
                r + room, so + send_own, sr + send_relay,
                *(jnp.where(slot_col == s, room, x) for x in rooms))

    zn = jnp.zeros_like(own0)
    z1 = jnp.zeros((n, 1), own0.dtype)
    rooms = (jnp.zeros((n, u), own0.dtype),) if vlb else ()
    d_own, d_relay, d_elig, r, so, sr, *rooms = jax.lax.fori_loop(
        0, u, direct, (zn, zn, zn, z1, z1, z1, *rooms))
    own = own0 + d_own
    relay = relay0 + d_relay
    moved = z1
    if vlb:
        elig = own + d_elig
        q = elig.sum(1, keepdims=True)
        t = jnp.minimum(q, r)
        frac = jnp.where(q > 0, t / jnp.maximum(q, 1e-30), 0.0)
        take = elig * frac
        inv_r = jnp.where(r > 0, 1.0 / jnp.maximum(r, 1e-30), 0.0)
        own = own - take
        shares = rooms[0] * inv_r
        take_hi, take_mid, take_lo = (p.astype(jnp.bfloat16)
                                      for p in _split3(take))
        part_col = jax.lax.broadcasted_iota(jnp.int32, (n, 3), 1)

        def spread(s, add):
            # relay[j] += share_s[dst[j, s]] * take[dst[j, s]]: both row
            # gathers are products with the one-hot (symmetric) hit_s.
            # The share's parts sit in one (N, 3) operand, re-summed
            # across lanes: exact in any order (`_split3`).
            onehot = hit_of(s).astype(jnp.bfloat16)
            share = jnp.where(slot_col == s, shares, 0.0).sum(1, keepdims=True)
            s_hi, s_mid, s_lo = _split3(share)
            parts = jnp.where(part_col == 0, s_hi,
                              jnp.where(part_col == 1, s_mid, s_lo))
            w = _dot(onehot, parts.astype(jnp.bfloat16)).sum(1, keepdims=True)
            row = ((_dot(onehot, take_hi) + _dot(onehot, take_mid))
                   + _dot(onehot, take_lo))
            return add + w * row

        relay = relay + jax.lax.fori_loop(0, u, spread, zn)
        moved = t

    own_o[0] = own
    relay_o[0] = relay
    col = jax.lax.broadcasted_iota(jnp.int32, (n, 3), 1)
    stats_o[0] = jnp.where(col == 0, so, jnp.where(col == 1, sr, moved))


def rotor_slice_fwd(
    own: jnp.ndarray,     # (B, N, N)
    relay: jnp.ndarray,   # (B, N, N)
    dst: jnp.ndarray,     # (N, u) int32
    vlb: bool, interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    bsz, n = own.shape[0], own.shape[1]
    u = dst.shape[1]
    state_spec = pl.BlockSpec((1, n, n), lambda b: (b, 0, 0))
    own2, relay2, stats = pl.pallas_call(
        functools.partial(_kernel, vlb=vlb),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((u, n), lambda b: (0, 0)),
            state_spec,
            state_spec,
        ],
        out_specs=[
            state_spec,
            state_spec,
            pl.BlockSpec((1, n, 3), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n, n), own.dtype),
            jax.ShapeDtypeStruct((bsz, n, n), own.dtype),
            jax.ShapeDtypeStruct((bsz, n, 3), own.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(n)),
        interpret=interpret,
    )(dst.T, own, relay)
    return (own2, relay2) + rack_totals(stats)
