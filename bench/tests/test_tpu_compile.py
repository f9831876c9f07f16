"""Each cell's device programs compiled for a described TPU v5e at the
cell's own shapes (no chip): what the chip's compiler would refuse shows
here, and the sparse step must hold the Pallas kernel.

The topology is described in a module fixture, never at import; the
persistent compilation cache is off around these compiles.
"""
import os

import pytest

CASES = {
    # cell: (batch, racks, rotor switches, cycles)
    "fluid-648-bulk": (48, 108, 6, 40),
    "fluid-5184-bulk": (8, 432, 12, 2),
}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dense_scan_fits(one_chip):
    import jax.numpy as jnp

    from repro.netsim import fluid_jax

    b, n, u, cycles = CASES["fluid-648-bulk"]
    c = fluid_jax._run_batch.lower(
        _sds(one_chip, (n, n, n), jnp.float32),
        _sds(one_chip, (b, n, n), jnp.float32), True, cycles).compile()
    m = c.memory_analysis()
    print("dense scan", m)
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 16e9


def test_sparse_step_holds_kernel(one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.kernels.rotor_slice import ops
    from repro.netsim import fluid_jax

    b, n, u, _ = CASES["fluid-5184-bulk"]
    st = _sds(one_chip, (b, n, n), jnp.float32)
    vec = _sds(one_chip, (b,), jnp.float32)
    # `ops` picks the kernel by the default backend, the CPU here: steer
    # it to the branch the chip takes
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret=None: False)
    jax.clear_caches()
    try:
        c = fluid_jax._sparse_slice_step.lower(
            st, st, vec, vec, _sds(one_chip, (n, u), jnp.int32),
            True).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    print("sparse step", m)
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 16e9


def test_reference_fits(one_chip):
    """The plain fluid reference at the larger cell's size."""
    import jax.numpy as jnp

    from bench.ref import fluid as ref

    b, n, u, cycles = CASES["fluid-5184-bulk"]
    for matmul in ("exact", "bf16x3"):
        c = ref._run.lower(_sds(one_chip, (n, n, n), jnp.float32),
                           _sds(one_chip, (b, n, n), jnp.float32),
                           num_cycles=cycles, matmul=matmul).compile()
        m = c.memory_analysis()
        print("reference", matmul, m)
        assert m.temp_size_in_bytes + m.argument_size_in_bytes < 16e9
