"""Quickstart: the paper's mechanism end-to-end in 60 seconds on CPU.

1. Build the 648-host Opera topology; show slices are expanders and every
   rack pair gets a direct circuit each cycle.
2. Run the two traffic classes through the fluid simulator.
3. Run the SAME schedule as a JAX collective: a rotor all-reduce syncing
   gradients of a tiny model (the TPU adaptation).
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.opera_paper import OPERA_648
from repro.core.expander import mean_max_path, spectral_gap
from repro.core.schedule import cycle_timing
from repro.core.topology import build_opera_topology
from repro.launch.mesh import auto_mesh
from repro.netsim.fluid import simulate_rotor_bulk
from repro.netsim.workloads import demand_all_to_all

print("== 1. Topology: expansion at every instant, direct circuits over time")
topo = build_opera_topology(108, 6, seed=0)
adj = topo.adjacency(0)
mean_h, max_h, disc = mean_max_path(adj)
print(f"   slice 0: mean path {mean_h:.2f}, max {max_h}, "
      f"spectral gap {spectral_gap(adj):.3f}, disconnected pairs {disc}")
ds = topo.direct_slice()
print(f"   every rack pair direct once/cycle: "
      f"{bool((ds[~np.eye(108, dtype=bool)] >= 0).all())}")
t = cycle_timing(OPERA_648)
print(f"   cycle {t.cycle_ms:.1f} ms, duty {100*t.duty_cycle:.1f}%, "
      f"bulk cutoff {t.bulk_cutoff_mb:.0f} MB  (paper: 10.7 ms / 98% / 15 MB)")

print("\n== 2. Bulk class: 100 KB shuffle rides tax-free direct circuits")
r = simulate_rotor_bulk(OPERA_648, demand_all_to_all(108, 6, 100e3),
                        vlb=False, max_cycles=40)
print(f"   99p FCT {r.fct_99_ms:.1f} ms (paper: 60 ms), "
      f"bandwidth tax {100*r.bandwidth_tax:.2f}%")

print("\n== 3. Same schedule as a JAX collective (rotor gradient sync)")
from repro.core import collectives as C  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

n = len(jax.devices())
mesh = auto_mesh((n, 1), ("data", "model"))
grads = jnp.arange(8.0 * n).reshape(n, 8)
rotor = jax.jit(jax.shard_map(
    lambda g: C.rotor_all_reduce(g, "data"),
    mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False,
))(grads)
want = jax.jit(jax.shard_map(
    lambda g: jax.lax.psum(g, "data"),
    mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False,
))(grads)
print(f"   rotor_all_reduce == psum: {bool(jnp.allclose(rotor, want))}")
print(f"   wire-byte ledger (N=16): {C.schedule_stats(16)}")
print("\nquickstart OK")
