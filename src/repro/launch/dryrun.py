"""Compile-only dry run of the production meshes on 512 fake CPU devices.

Counts HLO collective bytes and roofline terms from CPU compilations; it
measures nothing on an accelerator and never touches one (it pins
``JAX_PLATFORMS=cpu`` before jax is imported).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below this line may import jax ------------------------------
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.hlo import collective_breakdown_table, collective_bytes  # noqa: E402
from repro.analysis.hlo_cost import analyze as hlo_analyze  # noqa: E402
from repro.analysis.roofline import RooflineTerms, model_flops  # noqa: E402
from repro.configs import SHAPES, get_config, input_specs, list_archs  # noqa: E402
from repro.launch.mesh import make_production_mesh, pctx_for_mesh  # noqa: E402
from repro.models import model as MDL  # noqa: E402
from repro.models.kvcache import cache_specs  # noqa: E402
from repro.models.sharding import (  # noqa: E402
    batch_shardings,
    cache_shardings,
    param_shardings,
)
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train.trainer import make_train_step  # noqa: E402

"""Multi-pod dry-run: .lower().compile() for every (arch x shape x mesh).

Produces one JSON per cell under --out with:
  - memory_analysis (per-device argument/output/temp/code bytes)
  - cost_analysis (per-device HLO FLOPs / bytes accessed)
  - per-kind collective wire bytes parsed from the compiled SPMD module
  - the derived roofline terms (analysis/roofline.py)

This is the proof that the distribution config is coherent: a sharding
mismatch, an unsupported collective, or a size blow-up fails the compile.
"""


def _specced(tree_shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_shapes,
        shardings,
    )


def build_cell(arch: str, shape_name: str, mesh, pctx, opt_steps=10_000,
               cfg_overrides=None):
    cfg = get_config(arch)
    cfg = cfg.replace(
        grad_sync=pctx.grad_sync, moe_dispatch=pctx.moe_dispatch,
        **(cfg_overrides or {}),
    )
    spec = SHAPES[shape_name]
    pshapes = MDL.param_shapes(cfg)
    pshard = param_shardings(pshapes, cfg, pctx)
    params_sds = _specced(pshapes, pshard)
    batch_sds = batch_shardings(input_specs(cfg, spec), pctx)

    if spec.kind == "train":
        opt = AdamWConfig(total_steps=opt_steps)
        step_fn = make_train_step(cfg, pctx, opt)
        mzero = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, np.float32), pshapes
        )
        mshard = param_shardings(mzero, cfg, pctx)
        m_sds = _specced(mzero, mshard)
        state_sds = {
            "params": params_sds,
            "opt": {
                "m": m_sds,
                "v": m_sds,
                "step": jax.ShapeDtypeStruct(
                    (), np.int32, sharding=NamedSharding(mesh, P())
                ),
            },
        }
        return cfg, spec, jax.jit(step_fn), (state_sds, batch_sds)

    if spec.kind == "prefill":
        fn = lambda p, b: MDL.forward_prefill(p, b, cfg, pctx)  # noqa: E731
        return cfg, spec, jax.jit(fn), (params_sds, batch_sds)

    # decode
    B = spec.global_batch
    cspecs = cache_specs(cfg, B, spec.seq_len)
    cache_sds = cache_shardings(cspecs, pctx)
    tok = jax.ShapeDtypeStruct(
        (B, 1), np.int32, sharding=NamedSharding(mesh, P())
    )
    pos = jax.ShapeDtypeStruct(
        (B,), np.int32, sharding=NamedSharding(mesh, P())
    )
    fn = lambda p, t, q, c: MDL.forward_decode(p, t, q, c, cfg, pctx)  # noqa: E731
    return cfg, spec, jax.jit(fn), (params_sds, tok, pos, cache_sds)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             overrides=None, tag: str = "") -> dict:
    multi = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    chips = int(np.prod(list(mesh.shape.values())))
    cfg0 = get_config(arch)
    kw = dict(grad_sync=cfg0.grad_sync, moe_dispatch=cfg0.moe_dispatch)
    cfg_over = {}
    for key, val in (overrides or {}).items():
        if key in ("loss_chunk_vocab", "remat", "param_dtype", "norm_upcast"):
            if key == "loss_chunk_vocab":
                cfg_over[key] = int(val)
            elif key == "norm_upcast":
                cfg_over[key] = val not in ("0", "false", "False")
            else:
                cfg_over[key] = val
        else:
            kw[key] = val
    pctx = pctx_for_mesh(mesh, **kw)

    t0 = time.time()
    cfg, spec, jfn, args = build_cell(arch, shape_name, mesh, pctx,
                                      cfg_overrides=cfg_over)
    with jax.set_mesh(mesh):
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem_d[f] = int(getattr(mem, f, 0) or 0)
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # loop-aware costs (XLA's cost_analysis counts while bodies once; our
    # stacks are scans, so trip-count-corrected numbers are the real ones)
    la = hlo_analyze(hlo)
    flops = float(la["flops"])
    bytes_acc = float(la["bytes"])
    coll = collective_bytes(hlo)  # naive (loop bodies once) — kept for ref
    coll_total = float(la.get("coll_bytes_total", 0.0))

    n_params = MDL.count_params(cfg)
    n_active = MDL.count_params(cfg, active_only=True)
    terms = RooflineTerms(
        arch=arch,
        shape=shape_name,
        mesh=mesh_kind,
        chips=chips,
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        coll_bytes_per_device=coll_total,
        model_flops_total=model_flops(cfg, spec, n_params, n_active),
    )
    rec = dict(
        arch=arch,
        shape=shape_name,
        mesh=mesh_kind,
        chips=chips,
        status="ok",
        params=n_params,
        active_params=n_active,
        seconds_lower=t_lower,
        seconds_compile=t_compile,
        memory_analysis=mem_d,
        cost_flops=flops,
        cost_bytes=bytes_acc,
        xla_cost_analysis={k: float(cost.get(k, 0.0))
                           for k in ("flops", "bytes accessed")},
        loop_aware=la,
        collectives=coll,
        roofline=terms.row(),
        overrides=overrides or {},
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fname = out_dir / f"{arch}__{shape_name}__{mesh_kind}{suffix}.json"
    fname.write_text(json.dumps(rec, indent=1))
    print(
        f"[dryrun] {arch} x {shape_name} x {mesh_kind}{suffix}: OK "
        f"(lower {t_lower:.1f}s compile {t_compile:.1f}s) "
        f"flops/dev={flops:.3e} bytes/dev={bytes_acc:.3e} "
        f"coll/dev={coll_total:.3e} "
        f"dominant={terms.dominant} useful={terms.useful_flops_ratio:.2f}",
        flush=True,
    )
    if mem_d:
        tot = (mem_d.get("argument_size_in_bytes", 0)
               + mem_d.get("output_size_in_bytes", 0)
               + mem_d.get("temp_size_in_bytes", 0)
               - mem_d.get("alias_size_in_bytes", 0))
        print(f"memory_analysis: {mem_d} -> {tot/2**30:.2f} GiB/device", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--grad-sync", dest="grad_sync", default=None)
    ap.add_argument("--moe-dispatch", dest="moe_dispatch", default=None)
    ap.add_argument("--act-sharding", dest="act_sharding", default=None)
    ap.add_argument("--layout", default=None)
    ap.add_argument("--loss-chunk", dest="loss_chunk_vocab", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--param-dtype", dest="param_dtype", default=None)
    ap.add_argument("--norm-upcast", dest="norm_upcast", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    out_dir = Path(args.out)
    overrides = {}
    for k in ("grad_sync", "moe_dispatch", "act_sharding", "layout",
              "loss_chunk_vocab", "remat", "param_dtype", "norm_upcast"):
        v = getattr(args, k)
        if v:
            overrides[k] = v

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = (
            list(cfg.shapes) if args.shape == "all" else [args.shape]
        )
        for shape in shapes:
            if shape not in cfg.shapes:
                print(f"[dryrun] {arch} x {shape}: SKIP "
                      f"({cfg.skipped_shapes.get(shape, 'not in shape set')})")
                continue
            for mk in meshes:
                sfx = f"__{args.tag}" if args.tag else ""
                f = out_dir / f"{arch}__{shape}__{mk}{sfx}.json"
                if args.skip_existing and f.exists():
                    print(f"[dryrun] {arch} x {shape} x {mk}: cached")
                    continue
                try:
                    run_cell(arch, shape, mk, out_dir,
                             overrides or None, tag=args.tag)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mk, repr(e)))
                    print(f"[dryrun] {arch} x {shape} x {mk}: FAIL {e!r}",
                          flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUN CELLS PASSED")


if __name__ == "__main__":
    main()
