"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
described `v5e:2x2` topology.  These tests catch what interpret mode
cannot — block shapes Mosaic refuses, kernels that overflow VMEM,
programs that do not fit one chip — and pin that the Pallas rotor
kernel, not a substitute, is what the sparse engine's slice loop
compiles to.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every pytest
worker imports this file.  The persistent compilation cache is off
around these compiles, since entries written for a described chip cannot
be read back without one.
"""
import os

import pytest

# (num_racks, u): the paper's k12-n108 point and the k32-n432 point
KERNEL_POINTS = [(108, 6), (432, 16)]
BATCH = 4


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.mark.parametrize("vlb", [True, False])
@pytest.mark.parametrize("n,u", KERNEL_POINTS)
def test_rotor_kernel_compiles(sds, n, u, vlb):
    """The TPU path of `ops.rotor_slice_step` (one scenario per grid
    cell) passes Mosaic at paper widths and keeps the Pallas call."""
    import jax.numpy as jnp

    from repro.kernels.rotor_slice.ops import rotor_slice_step

    st = sds((BATCH, n, n))
    compiled = rotor_slice_step.lower(
        st, st, sds((n, u), jnp.int32), vlb=vlb, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sparse_engine_step_holds_kernel(sds, monkeypatch):
    """The sparse engine's slice-loop program at k32-n432 (432 slices a
    cycle, 2 cycles) runs the kernel itself: no reference math replaces
    it on the chip, and the whole loop fits one chip."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.rotor_slice import ops
    from repro.netsim import fluid_jax

    n, u = KERNEL_POINTS[1]
    # `ops` picks kernel or ref path by the default backend, the CPU
    # here; steer it to the branch the chip takes
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret=None: False)
    jax.clear_caches()
    try:
        compiled = fluid_jax._run_batch_sparse.lower(
            sds((n, n, u), jnp.int32), sds((BATCH, n, n)), True, 2).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 10**9


def test_dense_run_batch_compiles_k12_n108(sds):
    """The dense engine's whole scan at the paper's 648-host point (48
    scenarios, as chip_smoke.py runs it) fits one chip, with its VLB
    relay matmul pinned to HIGHEST precision."""
    from repro.netsim import fluid_jax

    n = slices = 108
    lowered = fluid_jax._run_batch.lower(
        sds((slices, n, n)), sds((48, n, n)), vlb=True, num_cycles=2)
    assert "precision = [HIGHEST, HIGHEST]" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 10**9
