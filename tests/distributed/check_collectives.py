"""Standalone multi-device check for the rotor collectives.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(set here defensively too — MUST be set before jax import).  Asserts rotor
collectives match their lax reference semantics on a (pod=2, data=4) mesh.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.core import collectives as C  # noqa: E402

mesh = auto_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)


def run(fn, x, in_spec, out_spec):
    f = jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
                         check_vma=False)
    return jax.jit(f)(x)


def check(name, got, want, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol,
                               rtol=1e-5, err_msg=name)
    print(f"ok: {name}")


# ---- rotor_all_reduce over data (4 shards), batch-sharded input ----------
x = rng.normal(size=(8, 6)).astype(np.float32)

got = run(lambda a: C.rotor_all_reduce(a, "data"), x, P("data", None),
          P("data", None))
want = run(lambda a: lax.psum(a, "data"), x, P("data", None), P("data", None))
check("rotor_all_reduce(rs_ag) == psum", got, want)

got = run(lambda a: C.rotor_all_reduce(a, "data", mode="direct"), x,
          P("data", None), P("data", None))
check("rotor_all_reduce(direct) == psum", got, want)

# ---- hierarchical over (data, pod) ---------------------------------------
x2 = rng.normal(size=(8, 4)).astype(np.float32)
got = run(lambda a: C.hierarchical_rotor_all_reduce(a, "data", "pod"), x2,
          P(("pod", "data"), None), P(("pod", "data"), None))
want = run(lambda a: lax.psum(a, ("pod", "data")), x2,
           P(("pod", "data"), None), P(("pod", "data"), None))
check("hierarchical_rotor_all_reduce == psum(pod,data)", got, want)

# ---- reduce-scatter / all-gather round trip ------------------------------
x3 = rng.normal(size=(8, 8)).astype(np.float32)  # per-shard (2, 8) -> 16 elts


def rs_ag(a):
    c = C.rotor_reduce_scatter(a, "data")
    full = C.rotor_all_gather(c, "data").reshape(-1)
    return full[: a.size].reshape(a.shape)


got = run(rs_ag, x3, P("data", None), P("data", None))
want = run(lambda a: lax.psum(a, "data"), x3, P("data", None), P("data", None))
check("rotor RS+AG == psum", got, want)

# ---- all-to-all (incl. VLB) ----------------------------------------------
# per-shard buffer (4, 3): chunk j destined for data-shard j
xa = rng.normal(size=(2, 4 * 4, 3)).astype(np.float32)  # sharded over pod too


def a2a_rotor(a):  # a: (1, 4, 3) per shard -> drop pod-local leading dim
    return C.rotor_all_to_all(a[0], "data")[None]


def a2a_ref(a):
    return lax.all_to_all(a, "data", split_axis=0, concat_axis=0, tiled=True)


got = run(a2a_rotor, xa, P("pod", "data", None), P("pod", "data", None))
want = run(lambda a: a2a_ref(a[0])[None], xa, P("pod", "data", None),
           P("pod", "data", None))
check("rotor_all_to_all == lax.all_to_all", got, want)

got = run(lambda a: C.rotor_all_to_all(a[0], "data", vlb=True)[None], xa,
          P("pod", "data", None), P("pod", "data", None))
check("rotor_all_to_all(vlb) == lax.all_to_all", got, want)

# ---- expander latency path ------------------------------------------------
xs = rng.normal(size=(8, 5)).astype(np.float32)
got = run(lambda a: C.expander_all_gather(a, "data", u=3), xs,
          P("data", None), P("data", None, None))
want = run(lambda a: lax.all_gather(a, "data"), xs, P("data", None),
           P("data", None, None))
check("expander_all_gather == all_gather", got, want)

got = run(lambda a: C.expander_psum_latency(a, "data"), xs, P("data", None),
          P("data", None))
want = run(lambda a: lax.psum(a, "data"), xs, P("data", None), P("data", None))
check("expander_psum_latency == psum", got, want)

# ---- compressed all-reduce: error feedback converges ----------------------
xc = rng.normal(size=(8, 16)).astype(np.float32)


def comp(a):
    total, err = C.compressed_rotor_all_reduce(a, "data", None, bits=8)
    return total


got = run(comp, xc, P("data", None), P("data", None))
want = run(lambda a: lax.psum(a, "data"), xc, P("data", None), P("data", None))
rel = np.abs(np.asarray(got) - np.asarray(want)).max() / np.abs(want).max()
assert rel < 0.05, f"int8 compressed AR too lossy: rel={rel}"
print(f"ok: compressed_rotor_all_reduce within int8 tolerance (rel={rel:.4f})")

# ---- wire-byte accounting sanity ------------------------------------------
st = C.schedule_stats(8, u=3)
assert st["rotor_a2a_vlb_bytes"] == 2 * st["rotor_a2a_bytes"]
assert st["bandwidth_tax_latency"] >= 1.0
print("ok: schedule_stats")

print("ALL COLLECTIVE CHECKS PASSED")
