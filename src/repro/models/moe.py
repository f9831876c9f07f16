"""Mixture-of-Experts with Opera-scheduled expert-parallel dispatch.

Experts are sharded over the `model` (TP) mesh axis; tokens are sharded
over data (batch) and, for train/prefill, over `model` (sequence).  The
dispatch/combine all-to-all along the expert axis is *exactly* the
paper's bulk shuffle: per-destination buffers queued at the source and
delivered on direct one-hop circuits.  `moe_dispatch` selects:

    rotor      — rotor_all_to_all (one-hop direct schedule, zero tax)
    rotor_vlb  — RotorLB 2-hop Valiant spreading (skew-proof, 100 % tax)
    xla        — lax.all_to_all baseline
    local      — no a2a (decode / replicated-activation path)

Routing is capacity-based (deterministic drop, GShard-style) so that all
buffer shapes are static — the "pre-configured matchings, no runtime
circuit selection" property of Opera carried into the collective layer.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import collectives as C
from repro.models.layers import act_fn, dense_init
from repro.models.parallel import ParallelContext


# ---------------- params ---------------------------------------------------


def init_moe(key, cfg: ModelConfig) -> Dict:
    m = cfg.moe
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    E, D, F = m.num_experts, cfg.d_model, m.d_ff_expert
    p = {
        "router": dense_init(ks[0], D, E, jnp.float32),  # fp32 router
        "w_gate": jax.vmap(lambda k: dense_init(k, D, F, dt))(
            jax.random.split(ks[1], E)
        ),
        "w_up": jax.vmap(lambda k: dense_init(k, D, F, dt))(
            jax.random.split(ks[2], E)
        ),
        "w_down": jax.vmap(lambda k: dense_init(k, F, D, dt))(
            jax.random.split(ks[3], E)
        ),
    }
    if m.num_shared_experts:
        Fs = m.d_ff_shared
        p["shared_gate"] = dense_init(ks[4], D, Fs, dt)
        p["shared_up"] = dense_init(ks[5], D, Fs, dt)
        p["shared_down"] = dense_init(ks[6], Fs, D, dt)
    return p


# ---------------- routing helpers (per-shard, pure jnp) ---------------------


def _topk_route(logits: jnp.ndarray, k: int):
    """softmax -> top-k -> renormalize (Qwen3/DeepSeek norm_topk_prob)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    gates, idx = lax.top_k(probs, k)                              # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _rank_within_expert(e_flat: jnp.ndarray, E: int) -> jnp.ndarray:
    """rank[i] = #earlier slots assigned to the same expert (stable)."""
    Tk = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank_sorted = jnp.arange(Tk) - starts[sorted_e]
    rank = jnp.zeros((Tk,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return rank


def _dispatch_combine_local(
    x_tok: jnp.ndarray,  # (T, D)
    gates: jnp.ndarray,  # (T, k)
    idx: jnp.ndarray,    # (T, k)
    wg, wu, wd,          # (E_loc, D, F), ..., (E_loc, F, D)
    cfg: ModelConfig,
    capacity: int,
    a2a=None,            # callable (n, E_loc, C, D)->same, or None for local
    n_shards: int = 1,
    expert_offset: Optional[jnp.ndarray] = None,
):
    """Capacity-dispatch, (optional) a2a, per-expert FFN, combine."""
    m = cfg.moe
    E = m.num_experts
    T, D = x_tok.shape
    k = idx.shape[1]
    f = act_fn(cfg.act)

    e_flat = idx.reshape(-1)
    g_flat = gates.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(T), k)
    rank = _rank_within_expert(e_flat, E)
    keep = rank < capacity
    slot = jnp.where(keep, e_flat * capacity + rank, E * capacity)

    buf = jnp.zeros((E * capacity + 1, D), x_tok.dtype)
    buf = buf.at[slot].set(x_tok[t_flat])
    buf = buf[:-1].reshape(E, capacity, D)

    if a2a is not None:
        E_loc = E // n_shards
        sent = a2a(buf.reshape(n_shards, E_loc, capacity, D))
        # sent[s] = buffer from source shard s for MY experts
        h = sent.transpose(1, 0, 2, 3).reshape(E_loc, n_shards * capacity, D)
    else:
        E_loc = wg.shape[0]
        if E_loc != E:
            # local mode with sharded experts: select my experts' buffers
            # expert_offset = E_loc * my_shard_index (traced)
            h = lax.dynamic_slice_in_dim(buf, expert_offset, E_loc, axis=0)
        else:
            h = buf

    # per-expert gated FFN (grouped GEMM; kernels/moe_gmm mirrors this)
    ge = jnp.einsum("ecd,edf->ecf", h, wg.astype(h.dtype))
    up = jnp.einsum("ecd,edf->ecf", h, wu.astype(h.dtype))
    out = jnp.einsum("ecf,efd->ecd", f(ge) * up, wd.astype(h.dtype))

    if a2a is not None:
        back = a2a(
            out.reshape(E_loc, n_shards, capacity, D).transpose(1, 0, 2, 3)
        )
        # back[s] = my tokens' outputs from expert shard s
        out_full = back.reshape(E, capacity, D)
    else:
        if E_loc != E:
            out_full = jnp.zeros((E, capacity, D), out.dtype)
            out_full = lax.dynamic_update_slice_in_dim(
                out_full, out, expert_offset, axis=0
            )
        else:
            out_full = out

    flat = jnp.concatenate(
        [out_full.reshape(E * capacity, D), jnp.zeros((1, D), out.dtype)], axis=0
    )
    y_slots = flat[slot] * (g_flat * keep)[:, None].astype(out.dtype)
    y = jnp.zeros((T, D), out.dtype).at[t_flat].add(y_slots)
    return y


def _aux_loss(probs: jnp.ndarray, idx: jnp.ndarray, E: int) -> jnp.ndarray:
    """Switch-style load-balance loss: E * sum_e f_e * P_e (local view;
    globally averaged by the caller over the latency path)."""
    T, k = idx.shape
    f_e = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
    P_e = probs.mean(axis=0)
    return E * jnp.sum(f_e * P_e)


# ---------------- public apply ----------------------------------------------


def apply_moe(
    p: Dict,
    x: jnp.ndarray,  # (B, S, D)
    cfg: ModelConfig,
    pctx: ParallelContext,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss).  Routed experts + optional shared branch."""
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.num_experts, m.top_k

    # shared/always-on branch (DeepSeekMoE)
    shared = 0.0
    if m.num_shared_experts:
        f = act_fn(cfg.act)
        g = f(x @ p["shared_gate"].astype(x.dtype))
        u = x @ p["shared_up"].astype(x.dtype)
        shared = (g * u) @ p["shared_down"].astype(x.dtype)

    tp = pctx.tp_size
    use_a2a = tp > 1 and S % tp == 0 and S > 1

    if pctx.mesh is None or tp == 1:
        # single-shard path (smoke tests): no communication
        T = B * S
        capacity = _capacity(T, k, E, m.capacity_factor)
        logits = x.reshape(T, D).astype(jnp.float32) @ p["router"]
        gates, idx, probs = _topk_route(logits, k)
        y = _dispatch_combine_local(
            x.reshape(T, D), gates, idx,
            p["w_gate"], p["w_up"], p["w_down"], cfg, capacity,
        ).reshape(B, S, D)
        return y + shared, _aux_loss(probs, idx, E)

    # NOTE: shard_map uses the AMBIENT mesh (jax.set_mesh / enclosing
    # shard_map) so the MoE dispatch nests inside the pod-manual rotor
    # gradient-sync region (trainer.py) without a concrete/abstract clash.
    dp = tuple(pctx.dp_axes)
    tp_axis = pctx.tp_axis
    E_loc = E // tp

    def a2a_fn(buf):  # (tp, E_loc, C, D) per shard
        if pctx.moe_dispatch == "xla":
            return lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        return C.rotor_all_to_all(
            buf, tp_axis, vlb=(pctx.moe_dispatch == "rotor_vlb")
        )

    if use_a2a:
        in_spec = P(dp, tp_axis, None)     # tokens sharded over dp x seq/tp

        def shard_fn(xl, router, wg, wu, wd):
            b, s, _ = xl.shape
            T = b * s
            capacity = _capacity(T, k, E, m.capacity_factor)
            logits = xl.reshape(T, D).astype(jnp.float32) @ router
            gates, idx, probs = _topk_route(logits, k)
            y = _dispatch_combine_local(
                xl.reshape(T, D), gates, idx, wg, wu, wd, cfg, capacity,
                a2a=a2a_fn, n_shards=tp,
            ).reshape(b, s, D)
            # aux loss: global mean via the latency-class expander path
            aux = _aux_loss(probs, idx, E)
            aux = C.expander_psum_latency(aux[None], tp_axis)[0]
            for ax in dp[::-1]:
                aux = C.expander_psum_latency(aux[None], ax)[0]
            aux = aux / (tp * pctx.dp_size)
            return y, aux

        y, aux = jax.shard_map(
            shard_fn,
            in_specs=(in_spec, P(), P(tp_axis, None, None),
                      P(tp_axis, None, None), P(tp_axis, None, None)),
            out_specs=(in_spec, P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        # decode path: tokens replicated over tp; each shard runs its local
        # experts only, partial outputs summed over tp (rotor-direct).
        in_spec = P(dp, None, None)

        def shard_fn(xl, router, wg, wu, wd):
            b, s, _ = xl.shape
            T = b * s
            capacity = _capacity(T, k, E, m.capacity_factor)
            logits = xl.reshape(T, D).astype(jnp.float32) @ router
            gates, idx, probs = _topk_route(logits, k)
            off = (lax.axis_index(tp_axis) * E_loc).astype(jnp.int32)
            y = _dispatch_combine_local(
                xl.reshape(T, D), gates, idx, wg, wu, wd, cfg, capacity,
                a2a=None, expert_offset=off,
            ).reshape(b, s, D)
            y = C.rotor_all_reduce(y, tp_axis, mode="direct")
            aux = _aux_loss(probs, idx, E)
            return y, aux

        y, aux = jax.shard_map(
            shard_fn,
            in_specs=(in_spec, P(), P(tp_axis, None, None),
                      P(tp_axis, None, None), P(tp_axis, None, None)),
            out_specs=(in_spec, P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    return y + shared, aux


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    c = int(np.ceil(T * k / E * cf))
    return max(4, ((c + 3) // 4) * 4)
