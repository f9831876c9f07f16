"""Smoke run of the main paths on a TPU, at paper scale and full width.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --four-chips  # four chips: the multi-chip path only

One process, phases in order; each prints one line with its name, its
check and its wall time (compilation included).  A failed check raises;
the other phases still run, then the script exits non-zero and prints
no result.  The last line of
standard output is one JSON object naming the device JAX ran on.

One chip:

* fluid-dense   k12-n108 (648 hosts), shuffle/permutation/skew x 2 loads
                x 8 seeds in one `run_design` call on the dense engine;
                one scenario per workload against the numpy oracle
                `fluid.simulate_rotor_bulk`.
* fluid-sparse  k32-n432 for a few cycles on the sparse engine, whose
                compiled slice loop must hold the Pallas kernel
                (`tpu_custom_call`), against the dense engine.
* flows         websearch + datamining at 648 hosts on the dense and the
                tiled flow engines: histograms bitwise equal, and the
                dense datamining scenario against `flows.simulate`.
* train         smollm-360m at published widths through
                `launch.train.main`, opera-dp trainer, 3 steps.
* serve         smollm-360m at published widths through
                `launch.serve.main`, 2048-token slot caches.

Four chips: opera-dp (rotor reduce-scatter/all-gather) against gspmd
(XLA collectives) through `launch.train.main` on a 4-chip host mesh,
same seed and batch, and `rotor_all_reduce` against `lax.psum`.

Data and weights come from fixed seeds; the script reads only the
repository's tracked files and starts no other process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# smollm-360m train shape on one v5e (16 GB): the largest batch x length
# whose opera-dp step fits, from the compile rehearsal
# (tests/test_tpu_compile.py sizes the same step).
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
SERVE_REQUESTS, SERVE_NEW_TOKENS, SERVE_CACHE = 4, 16, 2048


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def phase(name: str, fn, *args, **kw) -> bool:
    """Run one phase and print its line; a failure is reported and the
    remaining phases still run, so one run shows every fault."""
    t0 = time.perf_counter()
    try:
        detail = fn(*args, **kw)
    except Exception as e:  # noqa: BLE001 — reported, and fails the run
        traceback.print_exc()
        print(f"[phase] {name}: FAILED {type(e).__name__}: {e} "
              f"({time.perf_counter() - t0:.1f}s)", flush=True)
        return False
    print(f"[phase] {name}: ok {detail} ({time.perf_counter() - t0:.1f}s)",
          flush=True)
    return True


def _close(a, b, rtol, what):
    check(bool(np.isclose(a, b, rtol=rtol)), f"{what}: {a} vs {b}")


def _drift(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1.0)))


# ---------------------------------------------------------------------------
# fluid engines
# ---------------------------------------------------------------------------


def fluid_dense(k=12, num_racks=108, seeds=8, loads=(0.1, 0.3),
                max_cycles=40):
    """Paper-scale dense batch against the numpy oracle, at the
    tolerances of tests/test_netsim_jax.py."""
    from repro.core.topology import build_opera_topology
    from repro.netsim.fluid import simulate_rotor_bulk
    from repro.netsim.sweep import (DesignPoint, SweepSpec, run_design,
                                    scenario_demand)

    dp = DesignPoint(k=k, num_racks=num_racks, groups=1)
    workloads = ("shuffle", "permutation", "skew")
    spec = SweepSpec(designs=(dp,), workloads=workloads, loads=loads,
                     seeds=tuple(range(seeds)), max_cycles=max_cycles,
                     engine="dense")
    rows, res = run_design(spec, dp)
    check(len(rows) == len(workloads) * len(loads) * seeds, "grid size")
    check(np.all(np.isfinite(res.finished_frac)), "finite trajectories")
    cfg = dp.to_config()
    topo = build_opera_topology(num_racks, cfg.u, seed=dp.topo_seed)
    per_w = len(loads) * seeds
    for w_i, w in enumerate(workloads):
        i = w_i * per_w + (len(loads) - 1) * seeds     # top load, seed 0
        a = simulate_rotor_bulk(
            cfg, scenario_demand(w, cfg, loads[-1], 0), vlb=spec.vlb,
            max_cycles=max_cycles, topo=topo)
        b = res.scenario(i)
        check(a.slices_run == b.slices_run,
              f"{w}: slices_run {a.slices_run} vs {b.slices_run}")
        for f in ("fct_mean_ms", "throughput_gbps", "goodput_bytes",
                  "wire_bytes"):
            _close(getattr(a, f), getattr(b, f), 1e-4, f"{w} {f}")
        if np.isfinite(a.fct_99_ms):
            _close(a.fct_99_ms, b.fct_99_ms, 1e-4, f"{w} fct_99_ms")
        else:
            check(not np.isfinite(b.fct_99_ms), f"{w}: fct_99_ms finite")
        check(abs(a.bandwidth_tax - b.bandwidth_tax) <= 1e-4,
              f"{w}: bandwidth_tax")
        check(np.allclose(a.finished_frac, b.finished_frac, atol=1e-5),
              f"{w}: finished_frac trajectory")
    return (f"{dp.name} B={len(rows)} oracle match x{len(workloads)} "
            f"max_cycles={max_cycles}")


def kernel_in_sparse_step(batch, num_slices, num_racks, u, max_cycles):
    """The sparse engine's compiled slice-loop program holds the Pallas
    kernel: no substitute runs in its place on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.netsim import fluid_jax

    dst = jax.ShapeDtypeStruct((num_slices, num_racks, u), jnp.int32)
    own = jax.ShapeDtypeStruct((batch, num_racks, num_racks), jnp.float32)
    text = fluid_jax._run_batch_sparse.lower(
        dst, own, True, max_cycles).compile().as_text()
    check("tpu_custom_call" in text, "tpu_custom_call in _run_batch_sparse")


def fluid_sparse(k=32, num_racks=432, seeds=2, max_cycles=2):
    """k32-n432 on the sparse engine vs the dense engine, same point."""
    from repro.netsim.sweep import DesignPoint, SweepSpec, run_design

    dp = DesignPoint(k=k, num_racks=num_racks, groups=1)
    spec = dict(designs=(dp,), workloads=("shuffle", "skew"), loads=(0.3,),
                seeds=tuple(range(seeds)), max_cycles=max_cycles)
    _, sparse = run_design(SweepSpec(**spec, engine="sparse"), dp)
    kernel_in_sparse_step(
        sparse.batch_size, sparse.finished_frac.shape[1] // max_cycles,
        num_racks, k // 2, max_cycles)
    _, dense = run_design(SweepSpec(**spec, engine="dense"), dp)
    worst = 0.0
    for f in ("finished_frac", "goodput_bytes", "wire_bytes",
              "residual_bytes"):
        d = _drift(getattr(dense, f), getattr(sparse, f))
        check(d < 1e-5, f"sparse vs dense {f}: drift {d:.2e}")
        worst = max(worst, d)
    check(float(sparse.finished_frac[:, -1].min()) > 0, "bytes delivered")
    return (f"{dp.name} B={sparse.batch_size} slices="
            f"{sparse.finished_frac.shape[1]} tpu_custom_call present "
            f"sparse~dense drift {worst:.1e}")


# ---------------------------------------------------------------------------
# flow engines
# ---------------------------------------------------------------------------


def flow_engines(num_hosts=648, load=0.25, horizon_s=1.0, tail_s=0.25):
    """Dense vs tiled flow engines (histograms bitwise) and the dense
    datamining scenario vs the numpy oracle, at the tolerances of
    tests/test_flows_jax.py."""
    from repro.netsim import flows
    from repro.netsim.flows_jax import simulate_flows_batch

    kw = dict(num_hosts=num_hosts, horizon_s=horizon_s, tail_s=tail_s)
    workloads = ("websearch", "datamining")
    scns = [flows.build_scenario("opera", w, load, seed=0, **kw)
            for w in workloads]
    dense = simulate_flows_batch(scns, engine="dense")
    tiled = simulate_flows_batch(scns, engine="tiled")
    for w, d, t in zip(workloads, dense.hists, tiled.hists):
        check(np.array_equal(d, t), f"{w}: dense/tiled histograms differ")
        check(int(d.sum()) > 0, f"{w}: no flow finished")
    o = flows.simulate("opera", "datamining", load, seed=0, **kw)
    r = dense.results[1]
    check(o.admitted == r.admitted, "datamining admitted")
    check(abs(o.finished_frac - r.finished_frac) <= 1e-6,
          f"finished_frac {o.finished_frac} vs {r.finished_frac}")
    check(abs(o.backlog_frac - r.backlog_frac) <= 1e-4, "backlog_frac")
    for f in ("fct_p99_ms_small", "fct_p99_ms_mid", "fct_p99_ms_large",
              "fct_mean_ms"):
        a, b = getattr(o, f), getattr(r, f)
        if np.isfinite(a) or np.isfinite(b):
            check(bool(np.isclose(a, b, rtol=1e-3, atol=1e-3)),
                  f"datamining {f}: {a} vs {b}")
    flows_n = "+".join(str(s.num_flows) for s in scns)
    return (f"{num_hosts} hosts load={load} flows={flows_n} "
            f"steps={scns[0].steps} hists bitwise, oracle match")


# ---------------------------------------------------------------------------
# model stack
# ---------------------------------------------------------------------------


def _train_argv(trainer, extra):
    return ["--arch", "smollm-360m", "--steps", "3", "--batch",
            str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ), "--trainer", trainer,
            "--log-every", "1", "--seed", "0"] + list(extra)


def train(extra=()):
    from repro.launch import train as launch_train

    out = launch_train.main(_train_argv("opera-dp", extra))
    losses = out["losses"]
    check(len(losses) == 3 and np.all(np.isfinite(losses)),
          f"losses {losses}")
    return (f"smollm-360m opera-dp batch={TRAIN_BATCH} seq={TRAIN_SEQ} "
            f"losses={[round(x, 4) for x in losses]}")


def serve(extra=()):
    from repro.launch import serve as launch_serve

    n_req, want = SERVE_REQUESTS, SERVE_NEW_TOKENS
    done = launch_serve.main(
        ["--arch", "smollm-360m", "--requests", str(n_req), "--slots",
         str(n_req), "--max-new", str(want), "--max-seq", str(SERVE_CACHE)]
        + list(extra))
    check(len(done) == n_req, f"{len(done)} of {n_req} requests finished")
    for r in done:
        check(len(r.out_tokens) == want,
              f"request {r.rid}: {len(r.out_tokens)} of {want} tokens")
    return f"smollm-360m {n_req} requests x {want} tokens"


def four_chips(extra=(), rtol=2e-3):
    """opera-dp vs gspmd on one 4-device host mesh, same seed and batch;
    rotor_all_reduce vs psum on an array sharded over the same 4."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import collectives as C
    from repro.launch import train as launch_train
    from repro.launch.mesh import auto_mesh

    n = len(jax.devices())
    check(n == 4, f"{n} devices, want 4")
    runs = {t: launch_train.main(_train_argv(t, extra))
            for t in ("opera-dp", "gspmd")}
    for t, out in runs.items():
        check(out["param_devices"] == n,
              f"{t}: params on {out['param_devices']} devices")
        check(np.all(np.isfinite(out["losses"])), f"{t}: losses")
    a, b = (np.asarray(runs[t]["losses"]) for t in ("opera-dp", "gspmd"))
    check(np.allclose(a, b, rtol=rtol), f"opera-dp {a} vs gspmd {b}")

    mesh = auto_mesh((n,), ("d",))
    x = jax.device_put(
        jnp.arange(n * 8 * 128, dtype=jnp.float32).reshape(n * 8, 128),
        NamedSharding(mesh, P("d")))
    check(len(x.sharding.device_set) == n, "input spans the mesh")

    def run(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("d"),
                                     out_specs=P("d"), check_vma=False))(x)

    rotor = run(lambda v: C.rotor_all_reduce(v, "d"))
    want = run(lambda v: jax.lax.psum(v, "d"))
    check(np.allclose(np.asarray(rotor), np.asarray(want), rtol=1e-6),
          "rotor_all_reduce != psum")
    return (f"mesh {n} devices, opera-dp losses {a.round(4).tolist()} "
            f"gspmd {b.round(4).tolist()}, rotor_all_reduce == psum")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"[phase] device: ok {dev.device_kind} x{len(jax.devices())} "
          f"cache={cache} ({time.perf_counter() - t0:.1f}s)", flush=True)

    if args.four_chips:
        phases = [("four-chips", four_chips)]
    else:
        phases = [("fluid-dense", fluid_dense), ("fluid-sparse", fluid_sparse),
                  ("flows", flow_engines), ("train", train), ("serve", serve)]
    failed = [name for name, fn in phases if not phase(name, fn)]
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
