"""Plain reference of Opera's rotor fluid recurrence (bulk traffic at
rack level, RotorLB two-hop VLB), in jax.numpy float32.

Per slice, with `adj` the slice's live circuits (1 = one slice of one
link's bytes, the unit everything is counted in):

1. own traffic drains over its direct circuit;
2. relayed traffic drains over its direct circuit with the room left;
3. VLB: backlog with no circuit this slice is offered, in proportion,
   to every partner with spare room: ``relay += share.T @ take``.

The relay product is the one matrix product; `matmul` chooses how it is
computed: `exact` at HIGHEST precision, or `bf16x3`, the three-pass
bfloat16 product (the precision one step below), written out so that it
means the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def exact(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def bf16x3(a, b):
    """a @ b from bfloat16 parts, dropping the lo x lo term, with float32
    accumulation: what `Precision.HIGH` computes on a TPU.  The parts are
    rounded with `reduce_precision`, which no compiler may fold away, and
    multiplied exactly (HIGHEST on bfloat16-exact values)."""
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)
    return exact(ah, bh) + (exact(ah, bl) + exact(al, bh))


MATMULS = {"exact": exact, "bf16x3": bf16x3}


def _slice(state, adj, matmul):
    own, relay, done, wire = state
    send_own = jnp.minimum(own, adj)
    own = own - send_own
    room = adj - send_own
    send_relay = jnp.minimum(relay, room)
    relay = relay - send_relay
    room = room - send_relay
    delivered = send_own.sum() + send_relay.sum()
    elig = jnp.where(adj > 0, 0.0, own)
    q = elig.sum(1)
    r = room.sum(1)
    t = jnp.minimum(q, r)
    take = elig * jnp.where(q > 0, t / jnp.where(q > 0, q, 1.0), 0.0)[:, None]
    share = room * jnp.where(r > 0, 1.0 / jnp.where(r > 0, r, 1.0), 0.0)[:, None]
    own = own - take
    relay = relay + matmul(share.T, take)
    done = done + delivered
    wire = wire + delivered + t.sum()
    return (own, relay, done, wire), (done, wire)


@functools.partial(jax.jit, static_argnames=("num_cycles", "matmul"))
def _run(adj, own0, num_cycles: int, matmul: str):
    mm = MATMULS[matmul]

    def one(own):
        z = jnp.zeros((), jnp.float32)
        state = (own, jnp.zeros_like(own), z, z)

        def cycle(s, _):
            return jax.lax.scan(lambda c, a: _slice(c, a, mm), s, adj)

        _, (done, wire) = jax.lax.scan(cycle, state, None, length=num_cycles)
        return done.reshape(-1), wire.reshape(-1)

    return jax.vmap(one)(own0)


def trajectories(adj: np.ndarray, demand: np.ndarray, cap: float,
                 num_cycles: int, matmul: str = "exact"):
    """Cumulative delivered and wire bytes after every slice, (B, T)
    float64, for (B, N, N) rack demand in bytes; `cap` is one slice of
    one circuit in bytes."""
    own0 = jnp.asarray(np.asarray(demand, np.float64) / cap, jnp.float32)
    done, wire = _run(jnp.asarray(adj, jnp.float32), own0, num_cycles,
                      matmul)
    return (np.asarray(done, np.float64) * cap,
            np.asarray(wire, np.float64) * cap)
