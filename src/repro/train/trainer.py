"""Train-step construction: loss -> grads -> (Opera-scheduled) sync -> AdamW.

Two gradient-sync regimes (DESIGN.md §3.1):

* ``xla``    — params are FSDP-sharded over data (and replicated over pod);
               GSPMD's automatically-inserted reduce-scatter/all-reduce is
               the baseline collective schedule.
* ``rotor``  — the inter-pod reduction is performed *explicitly* by the
               rotor schedule: the whole grad/update pipeline runs inside a
               partial `shard_map` that binds only the `pod` axis (data and
               model stay auto/GSPMD inside), and the pod all-reduce is
               `rotor_all_reduce(..., mode="direct")` — one direct exchange
               per matching, Opera's bulk class.  Scalar metrics ride the
               latency class (`expander_psum_latency`).

`make_train_step(cfg, pctx, opt)` returns a pure (state, batch) -> (state,
metrics) suitable for jit with NamedShardings (launch/dryrun.py and
launch/train.py) or for single-device use in tests.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import collectives as C
from repro.models.model import loss_fn
from repro.models.parallel import ParallelContext
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state


def make_train_step(cfg: ModelConfig, pctx: ParallelContext, opt: AdamWConfig):
    use_rotor_pod = (
        cfg.grad_sync == "rotor"
        and pctx.pod_axis is not None
        and pctx.mesh is not None
    )

    def grads_and_metrics(params, batch, inner_pctx):
        (total, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, inner_pctx), has_aux=True
        )(params)
        return grads, metrics

    if not use_rotor_pod:

        def train_step(state, batch):
            grads, metrics = grads_and_metrics(state["params"], batch, pctx)
            new_params, new_opt, om = adamw_update(
                opt, state["params"], grads, state["opt"]
            )
            metrics.update(om)
            return {"params": new_params, "opt": new_opt}, metrics

        return train_step

    # ---- explicit rotor inter-pod DDP -------------------------------------
    pod = pctx.pod_axis
    n_pod = int(pctx.mesh.shape[pod])
    # inside the pod-manual region the model sees only the intra-pod axes
    inner_pctx = ParallelContext(
        mesh=pctx.mesh,
        dp_axes=tuple(a for a in pctx.dp_axes if a != pod),
        tp_axis=pctx.tp_axis,
        pod_axis=None,
        moe_dispatch=pctx.moe_dispatch,
        grad_sync="xla",
        act_sharding=pctx.act_sharding,
    )

    def train_step(state, batch):
        def per_pod(params, opt_state, b):
            grads, metrics = grads_and_metrics(params, b, inner_pctx)
            # bulk class: gradients, one direct exchange per pod matching
            grads = jax.tree.map(
                lambda g: C.rotor_all_reduce(g, pod, mode="direct") / n_pod,
                grads,
            )
            # latency class: scalar telemetry crosses pods immediately
            metrics = {
                k: C.expander_psum_latency(v[None], pod)[0] / n_pod
                for k, v in metrics.items()
            }
            new_params, new_opt, om = adamw_update(opt, params, grads, opt_state)
            metrics.update(om)
            return new_params, new_opt, metrics

        # bind ONLY the pod axis; data/model stay GSPMD-auto inside
        rep = P()  # params replicated across pods (sharded inside by auto axes)
        fn = jax.shard_map(
            per_pod,
            mesh=pctx.mesh,
            in_specs=(rep, rep, P(pod)),
            out_specs=(rep, rep, rep),
            axis_names={pod},
            check_vma=False,
        )
        batch_specced = jax.tree.map(lambda x: x, batch)
        new_params, new_opt, metrics = fn(
            state["params"], state["opt"], batch_specced
        )
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def init_train_state(cfg: ModelConfig, params) -> Dict[str, Any]:
    return {"params": params, "opt": init_opt_state(params)}
