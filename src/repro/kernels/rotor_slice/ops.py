"""jit'd public wrapper for the permutation-sparse rotor slice step."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.rotor_slice.kernel import rotor_slice_fwd
from repro.kernels.rotor_slice.ref import rotor_slice_ref


@functools.partial(
    jax.jit, static_argnames=("vlb", "interpret", "force_pallas"))
def rotor_slice_step(
    own: jnp.ndarray,     # (B, N, N) undelivered bytes, normalized units
    relay: jnp.ndarray,   # (B, N, N) in-flight relayed bytes
    dst: jnp.ndarray,     # (N, u) int32 destination indices, sentinel N
    vlb: bool = True,
    interpret: Optional[bool] = None,
    force_pallas: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One Opera slice over a scenario batch; returns (own, relay,
    delivered, moved) with (B,) delivered / VLB-spread totals.

    On TPU the Pallas kernel always runs, one scenario per grid cell.
    Elsewhere (``interpret`` resolves True) the jnp math of
    `ref.rotor_slice_ref` runs instead: the Pallas interpreter walks the
    grid one scenario at a time, far slower than the batched jnp graph
    on XLA CPU.  ``force_pallas=True`` routes through
    ``pl.pallas_call(interpret=True)`` anyway — the kernel-exercise mode
    the parity tests use.
    """
    interpret = resolve_interpret(interpret)
    if interpret and not force_pallas:
        return rotor_slice_ref(own, relay, dst, vlb=vlb)
    return rotor_slice_fwd(own, relay, dst, vlb=vlb, interpret=interpret)
