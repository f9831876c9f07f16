"""Serving launcher: continuous-batching engine over a registered arch.

Full width on one chip (smollm-360m, 2048-token slot caches):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --requests 4 --slots 4 --max-new 16 --max-seq 2048

CPU-scale smoke: add --reduced (d_model 64).  `main` returns the
finished requests.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.models.parallel import single_device_ctx
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    params = init_params(cfg, jax.random.key(args.seed))
    eng = ServeEngine(cfg, params, single_device_ctx(), slots=args.slots,
                      max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for rid in range(args.requests):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(
                0, cfg.vocab_size, int(rng.integers(4, 16))
            ).astype(np.int32),
            max_new_tokens=args.max_new,
        ))
    done = eng.run_to_completion()
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {cfg.name}: {len(done)} requests, {toks} tokens, "
          f"{dt:.1f}s ({toks/dt:.1f} tok/s)")
    for r in done[:4]:
        print(f"  req {r.rid}: {r.prompt[:6].tolist()}... -> {r.out_tokens}")
    return done


if __name__ == "__main__":
    main()
