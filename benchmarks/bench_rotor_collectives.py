"""Framework-side benchmark: rotor-collective wire bytes vs theory.

Runs a subprocess with 8 fake CPU devices (``JAX_PLATFORMS=cpu``, so it
never reaches for an accelerator), compiles the rotor/XLA collective
variants, and compares the CPU HLO's per-device wire bytes
(loop-aware HLO accounting) against the closed-form schedule_stats —
the bandwidth-tax ledger of the TPU adaptation.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import banner, check, save
from repro.core.collectives import schedule_stats

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import auto_mesh
from repro.core import collectives as C
from repro.analysis.hlo_cost import analyze

mesh = auto_mesh((8,), ("d",))
N = 8
SZ = 1 << 14  # floats per shard

def wire(fn, shape):
    f = jax.shard_map(fn, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
                      check_vma=False)
    spec = jax.ShapeDtypeStruct(shape, jnp.float32)
    hlo = jax.jit(f).lower(spec).compile().as_text()
    return analyze(hlo)["coll_bytes_total"]

out = {}
out["rotor_ar"] = wire(lambda x: C.rotor_all_reduce(x, "d"), (8 * N, SZ // N))
out["rotor_ar_direct"] = wire(
    lambda x: C.rotor_all_reduce(x, "d", mode="direct"), (8 * N, SZ // N))
out["xla_ar"] = wire(lambda x: lax.psum(x, "d"), (8 * N, SZ // N))
out["rotor_a2a"] = wire(lambda x: C.rotor_all_to_all(x[0], "d")[None],
                        (8, N, SZ // N))
out["rotor_a2a_vlb"] = wire(
    lambda x: C.rotor_all_to_all(x[0], "d", vlb=True)[None], (8, N, SZ // N))
out["xla_a2a"] = wire(
    lambda x: lax.all_to_all(x, "d", split_axis=0, concat_axis=0, tiled=True),
    (8 * N, SZ // N))
out["expander_ag_u3"] = wire(lambda x: C.expander_all_gather(x, "d", u=3),
                             (8, SZ // N))
out["xla_ag"] = wire(lambda x: lax.all_gather(x, "d"), (8, SZ // N))
out["payload_bytes"] = float(SZ * 4)
print(json.dumps(out))
"""


def run() -> dict:
    banner("Rotor collectives — measured wire bytes vs schedule theory (N=8)")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        print(r.stdout, r.stderr)
        raise RuntimeError("collective bench subprocess failed")
    meas = json.loads(r.stdout.strip().splitlines()[-1])
    th = schedule_stats(8, u=3)
    payload = meas["payload_bytes"]

    rows = []
    def row(name, measured, theory_ratio):
        ratio = measured / payload
        rows.append(dict(op=name, measured_bytes=measured,
                         measured_ratio=ratio, theory_ratio=theory_ratio))
        print(f"  {name:18s} {measured:12.3e} B  ratio {ratio:6.2f} "
              f"(theory {theory_ratio:.2f})")

    row("rotor_all_reduce", meas["rotor_ar"], th["rotor_ar_bytes"])
    row("rotor_ar_direct", meas["rotor_ar_direct"], th["rotor_ar_direct_bytes"])
    row("xla_psum", meas["xla_ar"], 2 * 7 / 8)
    row("rotor_all_to_all", meas["rotor_a2a"], th["rotor_a2a_bytes"])
    row("rotor_a2a_vlb", meas["rotor_a2a_vlb"], th["rotor_a2a_vlb_bytes"])
    row("xla_all_to_all", meas["xla_a2a"], 7 / 8)
    row("expander_ag_u3", meas["expander_ag_u3"],
        th["expander_allgather_bytes"])
    row("xla_all_gather", meas["xla_ag"], 7.0)

    ok1 = check("rotor A2A moves (N-1)/N of payload (one-hop direct, 0 tax)",
                abs(meas["rotor_a2a"] / payload - 7 / 8) < 0.15)
    ok2 = check("VLB exactly doubles wire bytes (100% tax, §3.4)",
                1.8 <= meas["rotor_a2a_vlb"] / meas["rotor_a2a"] <= 2.2)
    ok3 = check("latency-class all-gather pays the multi-hop tax",
                meas["expander_ag_u3"] > 1.5 * meas["xla_ag"])
    ok4 = check("rotor AR(rs+ag) within 2x of XLA psum wire bytes",
                meas["rotor_ar"] <= 2.0 * max(meas["xla_ar"], payload))
    return dict(rows=rows, theory=th,
                checks=dict(a2a=ok1, vlb=ok2, latency_tax=ok3, ar=ok4))


if __name__ == "__main__":
    save("bench_rotor_collectives", run())
