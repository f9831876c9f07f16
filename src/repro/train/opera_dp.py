"""Opera-DP: the fully-explicit data-parallel trainer.

The whole train step runs inside one `shard_map` over the DP axes: every
shard computes local grads with pure jnp, then

  bulk class    -> gradients via hierarchical rotor schedule
                   (reduce-scatter over `data`, direct exchange over
                   `pod`, all-gather over `data`) — every byte one hop
                   per phase, Opera's tax-free direct circuits;
  latency class -> scalar telemetry (loss/aux) via immediate multi-hop
                   expander gossip (`expander_psum_latency`);
  compression   -> optional int8 + error-feedback on the wire
                   (`compressed_rotor_all_reduce`), a beyond-paper
                   distributed-optimization trick.

Best suited to models whose params fit replicated (smollm-class); large
archs use the GSPMD trainer (train/trainer.py) where the rotor schedule
rides the pod axis and the MoE dispatch.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import collectives as C
from repro.models.model import loss_fn
from repro.models.parallel import ParallelContext, single_device_ctx
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state


def make_opera_dp_train_step(
    cfg: ModelConfig,
    pctx: ParallelContext,
    opt: AdamWConfig,
    compress: bool = False,
):
    mesh = pctx.mesh
    data_axis = pctx.dp_axes[-1]
    pod_axis = pctx.pod_axis
    n_shards = pctx.dp_size
    local_ctx = single_device_ctx()

    def per_shard(params, opt_state, err, batch):
        (_, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, local_ctx), has_aux=True
        )(params)

        if compress:
            def sync(g, e):
                tot, ne = C.compressed_rotor_all_reduce(g, data_axis, e)
                if pod_axis is not None:
                    tot = C.rotor_all_reduce(tot, pod_axis, mode="direct")
                return tot / n_shards, ne

            pairs = jax.tree.map(sync, grads, err)
            grads = jax.tree.map(lambda t: t[0], pairs,
                                 is_leaf=lambda x: isinstance(x, tuple))
            err = jax.tree.map(lambda t: t[1], pairs,
                               is_leaf=lambda x: isinstance(x, tuple))
        else:
            grads = jax.tree.map(
                lambda g: C.hierarchical_rotor_all_reduce(
                    g, data_axis, pod_axis
                ) / n_shards,
                grads,
            )

        # latency class: control-plane scalars cross the fabric immediately
        agg = {}
        for k, v in metrics.items():
            s = C.expander_psum_latency(v[None], data_axis)[0]
            if pod_axis is not None:
                s = C.expander_psum_latency(s[None], pod_axis)[0]
            agg[k] = s / n_shards

        new_params, new_opt, om = adamw_update(opt, params, grads, opt_state)
        agg.update(om)
        return new_params, new_opt, err, agg

    batch_spec = P(tuple(pctx.dp_axes))
    rep = P()
    mapped = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False,
    )

    def train_step(state: Dict[str, Any], batch):
        err = state.get("err")
        if err is None:
            err = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                               state["params"])
        new_params, new_opt, new_err, metrics = mapped(
            state["params"], state["opt"], err, batch
        )
        out = {"params": new_params, "opt": new_opt}
        if compress:
            out["err"] = new_err
        return out, metrics

    return train_step


def init_opera_dp_state(params, compress: bool = False):
    st = {"params": params, "opt": init_opt_state(params)}
    if compress:
        st["err"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
    return st
