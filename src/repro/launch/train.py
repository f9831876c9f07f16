"""Training launcher.

Full width on the local chips (one TPU v5e: smollm-360m, 32 layers,
d_model 960; the host mesh spans every local device):

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 3 --batch 8 --seq 1024 --trainer opera-dp

CPU-scale smoke: add --reduced (d_model 64) with e.g. --batch 8 --seq 64.
--mesh pod/multipod builds the production meshes of launch/mesh.py.
`main` returns the per-step losses and how many devices hold the
trained parameters.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import reduced_config
from repro.data.pipeline import SyntheticLM, device_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh, pctx_for_mesh
from repro.models import init_params
from repro.models.sharding import batch_spec, param_shardings
from repro.models.model import param_shapes
from repro.optim.adamw import AdamWConfig
from repro.train.checkpoint import Checkpointer
from repro.train.opera_dp import init_opera_dp_state, make_opera_dp_train_step
from repro.train.trainer import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="host", choices=["host", "pod", "multipod"])
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--trainer", default="opera-dp",
                    choices=["opera-dp", "gspmd"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if args.mesh == "host":
        mesh = make_host_mesh(model=args.tp)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")
    pctx = pctx_for_mesh(mesh)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 5))

    key = jax.random.key(args.seed)
    params = init_params(cfg, key)
    # the initial state is placed as the step returns it, so the step
    # compiles once, not again at step 1 for a new input sharding
    replicated = NamedSharding(mesh, P())
    if args.trainer == "opera-dp":
        state = jax.device_put(
            init_opera_dp_state(params, compress=args.compress_grads),
            replicated)
        step_fn = make_opera_dp_train_step(
            cfg, pctx, opt, compress=args.compress_grads
        )
    else:
        state = init_train_state(cfg, params)
        step_fn = make_train_step(cfg, pctx, opt)
        shardings = param_shardings(param_shapes(cfg), cfg, pctx)
        state = {
            "params": jax.device_put(state["params"], shardings),
            "opt": {
                "m": jax.device_put(state["opt"]["m"], shardings),
                "v": jax.device_put(state["opt"]["v"], shardings),
                "step": jax.device_put(state["opt"]["step"], replicated),
            },
        }
    # outputs keep the inputs' exact shardings: GSPMD may spell the same
    # layout differently (P(None, 'data') for P(None, 'data', None)),
    # which is a cache miss, so an unpinned step compiles a second time
    jitted = jax.jit(step_fn, out_shardings=(
        jax.tree.map(lambda x: x.sharding, state), None))

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state, start_step = ckpt.restore(state)
        print(f"[train] resumed from step {start_step}")

    src = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    bspec = {
        k: NamedSharding(mesh, batch_spec(k, (args.batch, args.seq), pctx))
        for k in ("tokens", "targets")
    }
    batches = device_batches(src, start_step, bspec)

    print(f"[train] {cfg.name} ({sum(x.size for x in jax.tree.leaves(params)):,}"
          f" params), mesh {dict(mesh.shape)}, trainer={args.trainer}, "
          f"floor={src.conditional_entropy():.3f} nats")
    t0 = time.time()
    # the device's loss scalars, read only at log steps and at the end:
    # a read every step would wait for the device every step
    step_losses = []
    with jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                batch = next(batches)
                state, metrics = jitted(state, batch)
            step_losses.append(metrics["loss"])
            if step % args.log_every == 0 or step == args.steps - 1:
                print(
                    f"[train] step {step:5d} loss {float(metrics['loss']):.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} "
                    f"lr {float(metrics['lr']):.2e} "
                    f"({(time.time() - t0):.1f}s)",
                    flush=True,
                )
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state)
    if ckpt:
        ckpt.save(args.steps, state, blocking=True)
    losses = [float(x) for x in jax.device_get(step_losses)]
    print(f"[train] done: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(floor {src.conditional_entropy():.3f})")
    leaf = jax.tree.leaves(state["params"])[0]
    return {"losses": losses, "param_devices": len(leaf.sharding.device_set)}


if __name__ == "__main__":
    main()
