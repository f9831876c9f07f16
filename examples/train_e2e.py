"""End-to-end driver: train a ~100M-param model for a few hundred steps.

Uses the REAL smollm-360m architecture at trimmed depth/width so that a
~100M-parameter model trains in CPU-minutes, with the opera-dp trainer
(explicit rotor gradient sync + latency-class telemetry), checkpointing
every 50 steps, and a resume demonstration.

    PYTHONPATH=src python examples/train_e2e.py [--steps 300]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_host_mesh, pctx_for_mesh
from repro.models import init_params
from repro.optim.adamw import AdamWConfig
from repro.train.checkpoint import Checkpointer
from repro.train.opera_dp import init_opera_dp_state, make_opera_dp_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    # ~100M params: smollm-360m trimmed to 6 layers / vocab 8192
    cfg = get_config("smollm-360m").replace(
        num_layers=6, vocab_size=8192, tie_embeddings=True
    )
    params = init_params(cfg, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    mesh = make_host_mesh()
    pctx = pctx_for_mesh(mesh)
    opt = AdamWConfig(lr=8e-4, warmup_steps=30, total_steps=args.steps)
    step_fn = jax.jit(make_opera_dp_train_step(cfg, pctx, opt))
    state = init_opera_dp_state(params)
    src = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=0)
    ck = Checkpointer(args.ckpt, keep=2)

    print(f"model: {n/1e6:.1f}M params | floor {src.conditional_entropy():.3f}"
          f" nats | uniform {np.log(cfg.vocab_size):.3f} nats")
    t0, losses = time.time(), []
    with jax.set_mesh(mesh):
        for i in range(args.steps):
            state, m = step_fn(
                state, jax.tree.map(jnp.asarray, src.batch_at(i))
            )
            losses.append(float(m["loss"]))
            if i % 20 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {losses[-1]:.4f} "
                      f"gnorm {float(m['grad_norm']):.2f} "
                      f"({time.time()-t0:.0f}s)", flush=True)
            if (i + 1) % 50 == 0:
                ck.save(i + 1, state)
    ck.wait()
    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"\nloss {first:.3f} -> {last:.3f} "
          f"(uniform {np.log(cfg.vocab_size):.3f}, "
          f"floor {src.conditional_entropy():.3f})")
    assert last < first - 0.4, "training failed to learn"
    print("train_e2e OK")


if __name__ == "__main__":
    main()
