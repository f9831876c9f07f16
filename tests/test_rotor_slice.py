"""Permutation-sparse rotor slice engine: index-tensor structure, kernel
trio parity, and sparse-vs-dense full-engine agreement.

Three contracts under test:

  1. `OperaTopology.matching_index_tensor()` is a lossless re-encoding of
     `matching_tensor()`: scattering ones along (i, dst[i, s]) rebuilds
     the dense adjacency exactly, every live entry is an involution, and
     grouped reconfiguration darkens (at least) `groups` whole columns
     per slice.
  2. The `kernels/rotor_slice` trio agrees with itself (Pallas
     interpret path vs jnp ref path, bitwise when compiled without FMA
     — the kernel's one-hot gathers are exact and it sums in the ref's
     order) and with the numpy oracle `fluid.rotor_slice_step`.
  3. The sparse batch drivers (`_run_batch_sparse`, and the faulted
     engine behind ``engine="sparse"``) match the dense scan engine on
     full trajectories, unfaulted and under a nonempty
     `FailureSchedule`, and their one-program slice loops match a plain
     host stepping of the slice step bit for bit.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.schedule import cycle_timing, slice_capacity_bytes
from repro.core.topology import build_opera_topology
from repro.netsim.faults import FailureEvent, FailureSchedule
from repro.netsim.fluid import rotor_slice_step as oracle_step
from repro.netsim.fluid_jax import simulate_rotor_bulk_batch
from repro.netsim.sweep import DesignPoint, scenario_demand

# the default Appendix-B design points staticcheck verifies (k, n, groups)
DESIGNS = [(12, 108, 1), (12, 108, 2), (8, 16, 1)]


def _topo(k, n, g):
    return build_opera_topology(n, k // 2, seed=0, groups=g)


# ---------------------------------------------------------------------------
# 1. index tensor <-> dense tensor round trip + structure
# ---------------------------------------------------------------------------


class TestIndexTensor:
    @pytest.mark.parametrize("k,n,g", DESIGNS)
    def test_round_trip_reconstructs_dense(self, k, n, g):
        topo = _topo(k, n, g)
        dst = topo.matching_index_tensor()
        dense = topo.matching_tensor()
        assert dst.dtype == np.int32
        assert dst.shape == (topo.num_slices, n, topo.num_switches)
        rebuilt = np.zeros_like(dense)
        t, i, s = np.nonzero(dst < n)
        rebuilt[t, i, dst[t, i, s]] = 1.0
        np.testing.assert_array_equal(rebuilt, dense)

    @pytest.mark.parametrize("k,n,g", DESIGNS)
    def test_live_entries_are_involutions(self, k, n, g):
        dst = _topo(k, n, g).matching_index_tensor()
        i = np.arange(n)
        for t in range(dst.shape[0]):
            for s in range(dst.shape[2]):
                col = dst[t, :, s]
                live = col < n
                # dst[dst[i, s], s] == i and no self-maps survive export
                assert np.array_equal(col[col[live]], i[live])
                assert not np.any(col[live] == i[live])

    @pytest.mark.parametrize("k,n,g", DESIGNS + [(8, 16, 2)])
    def test_dark_columns_cover_reconfiguring_group(self, k, n, g):
        """Each slice darkens whole columns for the `groups` switches
        mid-reconfiguration (all-sentinel); matchings that merely hold
        self-loops produce partial sentinels, never a short column."""
        dst = _topo(k, n, g).matching_index_tensor()
        for t in range(dst.shape[0]):
            fully_dark = int((dst[t] == n).all(axis=0).sum())
            assert fully_dark >= g, (t, fully_dark)

    def test_sentinel_marks_self_loops(self):
        """At k8-n16 some live matchings hold fixed points: the sentinel
        lands exactly where the dense adjacency row has no circuit on
        that switch's matching."""
        topo = _topo(8, 16, 1)
        dst = topo.matching_index_tensor()
        dense = topo.matching_tensor()
        # rows with a sentinel in a live (not fully-dark) column have
        # one fewer live circuit than fully-live rows
        for t in range(dst.shape[0]):
            live_cols = ~(dst[t] == 16).all(axis=0)
            row_live = (dst[t][:, live_cols] < 16).sum(axis=1)
            np.testing.assert_array_equal(row_live, dense[t].sum(axis=1))


# ---------------------------------------------------------------------------
# 2. kernel trio parity: Pallas interpret vs ref path vs numpy oracle
# ---------------------------------------------------------------------------


def test_split3_parts_are_bf16_and_sum_back_exactly():
    """The kernel's truncation split: each part is exact in bf16, and
    the parts re-sum to the input bit for bit, in the kernel's order and
    in any other, across 2**-100..2**10 and at the edge values."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.rotor_slice.kernel import _split3

    rng = np.random.default_rng(0)
    mant = rng.uniform(1.0, 2.0, 200_000)
    x = (mant * 2.0 ** rng.integers(-100, 11, mant.size)).astype(np.float32)
    edge = np.array([0.0, 1 / 3, np.uint32(0x3FFFFFFF).view(np.float32),
                     np.uint32(0x7F7FFFFF).view(np.float32) / 2**118],
                    np.float32)
    x = np.concatenate([x, edge, -x, -edge[1:]])
    hi, mid, lo = (np.asarray(p) for p in jax.jit(_split3)(jnp.asarray(x)))
    for p in (hi, mid, lo):
        assert p.dtype == np.float32
        back = np.asarray(jnp.asarray(p).astype(jnp.bfloat16)
                          .astype(jnp.float32))
        np.testing.assert_array_equal(back.view(np.uint32),
                                      p.view(np.uint32))
    assert not (hi.view(np.uint32) & 0xFFFF).any()
    for total in ((hi + mid) + lo, hi + (mid + lo), (hi + lo) + mid):
        np.testing.assert_array_equal(total.view(np.uint32),
                                      x.view(np.uint32))


def _kernel_state(kind, seed=0):
    """(own, relay) for 3 scenarios at 16 racks, zero diagonal: "uniform"
    over [0, 2) and [0, 1), or "wide", whose values carry full 24-bit
    mantissas across 2**-40..2**1."""
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        own = rng.uniform(0.0, 2.0, (3, 16, 16))
        relay = rng.uniform(0.0, 1.0, (3, 16, 16))
    else:
        own, relay = (rng.uniform(0.0, 1.0, (3, 16, 16))
                      * 2.0 ** rng.integers(-40, 2, (3, 16, 16))
                      for _ in range(2))
    own, relay = own.astype(np.float32), relay.astype(np.float32)
    for a in (own, relay):
        a[:, np.arange(16), np.arange(16)] = 0.0
    return own, relay


# Pallas-vs-ref cases: a bare slice index is the uniform state on the
# one-group fabric; tuples are (state, groups, slice)
PALLAS_CASES = [0, 3, 7, ("wide", 1, 3), ("wide", 1, 7), ("uniform", 2, 3),
                ("wide", 2, 6)]


def _case(t):
    return t if isinstance(t, tuple) else ("uniform", 1, t)


def _pallas_vs_ref_mismatches():
    """Per case and vlb, the elements of each output in which the Pallas
    kernel (interpret mode) and the ref path differ, bit for bit."""
    import jax.numpy as jnp

    from repro.kernels.rotor_slice import rotor_slice_step

    out = {}
    for t in PALLAS_CASES:
        kind, groups, s = _case(t)
        dst = jnp.asarray(_topo(8, 16, groups).matching_index_tensor()[s])
        own, relay = (jnp.asarray(a) for a in _kernel_state(kind))
        for vlb in (False, True):
            ref = rotor_slice_step(own, relay, dst, vlb=vlb)
            pal = rotor_slice_step(own, relay, dst, vlb=vlb,
                                   force_pallas=True)
            out[f"{kind}-{groups}-{s}-{vlb}"] = [
                int((np.asarray(a).view(np.uint32)
                     != np.asarray(b).view(np.uint32)).sum())
                for a, b in zip(ref, pal)]
    return out


@pytest.fixture(scope="module")
def pallas_vs_ref():
    """`_pallas_vs_ref_mismatches` in a child process compiled for a CPU
    without FMA.  XLA's CPU compiler always lets LLVM fuse a multiply
    into an add, and the two paths' VLB spread sums fuse different
    multiplies (the ref's first two slots become fma(w0, row0, w1*row1),
    the kernel's fma(w1, row1, w0*row0)), so with FMA the paths may
    differ by an ulp in `relay`.  Without it each op rounds alone."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_cpu_max_isa=AVX").strip(),
        PYTHONPATH=os.pathsep.join(
            [here, src, os.environ.get("PYTHONPATH", "")]))
    code = ("import json, test_rotor_slice as t; "
            "print(json.dumps(t._pallas_vs_ref_mismatches()))")
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


class TestKernelParity:
    @pytest.fixture(scope="class")
    def state(self):
        topo = _topo(8, 16, 1)
        dst = topo.matching_index_tensor()
        dense = topo.matching_tensor()
        return (dst, dense) + _kernel_state("uniform")

    @pytest.mark.parametrize("vlb", [False, True])
    @pytest.mark.parametrize("t", [
        c if isinstance(c, int) else pytest.param(c, id="-".join(map(str, c)))
        for c in PALLAS_CASES])
    def test_pallas_kernel_bitwise_matches_ref_path(self, pallas_vs_ref,
                                                    vlb, t):
        """Own, relay, delivered and moved, bit for bit: the kernel's
        one-hot gathers are exact and it sums in the ref's order."""
        kind, groups, s = _case(t)
        assert pallas_vs_ref[f"{kind}-{groups}-{s}-{vlb}"] == [0, 0, 0, 0]

    @pytest.mark.parametrize("vlb", [False, True])
    @pytest.mark.parametrize("t", [0, 3, 7])
    def test_op_matches_numpy_oracle(self, state, vlb, t):
        import jax.numpy as jnp

        from repro.kernels.rotor_slice import rotor_slice_step

        dst, dense, own, relay = state
        o2, r2, deliv, moved = rotor_slice_step(
            jnp.asarray(own), jnp.asarray(relay), jnp.asarray(dst[t]),
            vlb=vlb)
        for b in range(own.shape[0]):
            eo, er, ed, em = oracle_step(
                own[b].astype(np.float64), relay[b].astype(np.float64),
                dense[t].astype(np.float64), vlb=vlb)
            np.testing.assert_allclose(np.asarray(o2[b]), eo, atol=1e-5)
            np.testing.assert_allclose(np.asarray(r2[b]), er, atol=1e-5)
            assert np.isclose(float(deliv[b]), ed, atol=1e-4)
            assert np.isclose(float(moved[b]), em, atol=1e-4)


# ---------------------------------------------------------------------------
# 3. full-engine parity: sparse vs dense batch drivers
# ---------------------------------------------------------------------------

DP = DesignPoint(k=8, num_racks=16, groups=1)
DP_G2 = DesignPoint(k=8, num_racks=16, groups=2)


def _drift(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1.0)))


def _faults(cfg):
    return FailureSchedule(
        num_racks=cfg.num_racks, num_switches=cfg.u,
        events=(FailureEvent("link", ((1, 0), (5, 1)), onset_step=1,
                             detect_lag=2, recover_step=10),
                FailureEvent("tor", (3,), onset_step=2,
                             detect_lag=1, recover_step=12)))


def _host_loop(dst, own0, vlb, num_cycles, faulted=None, paced_cycles=0):
    """The sparse drivers' slice loop stepped from the host: one call per
    slice and cycle, trajectories stacked at the end."""
    import jax.numpy as jnp

    from repro.kernels.rotor_slice.ops import rotor_slice_step
    from repro.netsim.fluid_jax import _sparse_slice_step_faulted

    bsz = own0.shape[0]
    own = jnp.zeros_like(own0) if paced_cycles else own0
    relay = jnp.zeros_like(own0)
    done = wire = blk = jnp.zeros((bsz,), own0.dtype)
    g = jnp.zeros((), jnp.int32)
    done_t, wire_t = [], []
    for c in range(num_cycles):
        if c < paced_cycles:
            own = own + own0 * (1.0 / paced_cycles)
        for t in range(dst.shape[0]):
            if faulted is None:
                own, relay, delivered, moved = rotor_slice_step(
                    own, relay, dst[t], vlb=vlb)
                done = done + delivered
                wire = wire + delivered + moved
            else:
                own, relay, done, wire, blk, g = _sparse_slice_step_faulted(
                    own, relay, done, wire, blk, g, dst[t], *faulted, vlb)
            done_t.append(done)
            wire_t.append(wire)
    residual = own.sum((1, 2)) + relay.sum((1, 2))
    out = (jnp.stack(done_t, 1), jnp.stack(wire_t, 1), residual)
    return out if faulted is None else out + (blk,)


class TestEngineParity:
    def test_run_batch_trajectories_agree(self):
        """Unfaulted drivers on an overloaded skew batch: cumulative
        delivered/wire trajectories and residuals must agree slice by
        slice, not just in the totals."""
        import jax.numpy as jnp

        from repro.netsim.fluid_jax import _run_batch, _run_batch_sparse

        cfg = DP.to_config()
        topo = build_opera_topology(cfg.num_racks, cfg.u, seed=0)
        cap = slice_capacity_bytes(cfg, cycle_timing(cfg))
        dem = np.stack([scenario_demand("skew", cfg, 2.5, s)
                        for s in range(3)])
        own0 = jnp.asarray(dem / cap, jnp.float32)
        dense = _run_batch(jnp.asarray(topo.matching_tensor()), own0, True, 4)
        sparse = _run_batch_sparse(
            jnp.asarray(topo.matching_index_tensor()), own0, True, 4)
        assert np.asarray(dense[2]).max() > 0, "skew batch must not drain"
        for d, s in zip(dense, sparse):
            assert _drift(d, s) < 1e-5

    @pytest.mark.parametrize("dp", [DP, DP_G2], ids=["g1", "g2"])
    @pytest.mark.parametrize("vlb", [False, True])
    def test_faulted_engines_agree(self, dp, vlb):
        cfg = dp.to_config()
        topo = build_opera_topology(
            cfg.num_racks, cfg.u, seed=0, groups=cfg.groups)
        faults = _faults(cfg)
        dem = np.stack([scenario_demand("permutation", cfg, 0.5, s)
                        for s in range(2)])
        res = {
            engine: simulate_rotor_bulk_batch(
                cfg, dem, vlb=vlb, max_cycles=10, topo=topo,
                faults=faults, engine=engine)
            for engine in ("dense", "sparse")
        }
        for field in ("goodput_bytes", "wire_bytes", "residual_bytes"):
            d = getattr(res["dense"], field)
            s = getattr(res["sparse"], field)
            assert _drift(d, s) < 1e-5, field
        # blackholed is a small difference of large attempted/delivered
        # totals: normalize by total offered bytes, not by itself
        bh_d = np.asarray(res["dense"].blackholed_bytes)
        bh_s = np.asarray(res["sparse"].blackholed_bytes)
        if vlb:   # VLB spread commits bytes to every edge, lag included
            assert bh_d.max() > 0, "schedule must blackhole something"
        total = dem.sum(axis=(1, 2))
        assert float(np.max(np.abs(bh_d - bh_s) / total)) < 1e-6

    @pytest.mark.parametrize("faulted", [False, True],
                             ids=["clean", "faulted-paced"])
    @pytest.mark.parametrize("dp", [DP, DP_G2], ids=["g1", "g2"])
    @pytest.mark.parametrize("vlb", [False, True])
    def test_device_loop_bitwise_matches_host_loop(self, dp, vlb, faulted):
        """One program per call (scan over cycles and slices) gives the
        host-stepped loop's trajectories, residual and blackholed total
        bit for bit; the faulted case paces its demand over 2 of 4
        cycles."""
        import jax.numpy as jnp

        from repro.netsim.faults import compile_fault_masks
        from repro.netsim.fluid_jax import (
            _run_batch_sparse,
            _run_batch_sparse_faulted,
        )

        cfg = dp.to_config()
        topo = build_opera_topology(
            cfg.num_racks, cfg.u, seed=0, groups=cfg.groups)
        cap = slice_capacity_bytes(cfg, cycle_timing(cfg))
        dem = np.stack([scenario_demand("skew", cfg, 2.5, s)
                        for s in range(3)])
        own0 = jnp.asarray(dem / cap, jnp.float32)
        dst = jnp.asarray(topo.matching_index_tensor())
        if faulted:
            masks = compile_fault_masks(topo, _faults(cfg)).broadcast_to(3)
            ops = [jnp.asarray(getattr(masks, f)) for f in (
                "pair_switch", "up_onset", "up_detect", "up_recover",
                "tor_onset", "tor_detect", "tor_recover")]
            got = _run_batch_sparse_faulted(dst, ops[0], own0, *ops[1:],
                                            vlb, 4, 2)
            want = _host_loop(dst, own0, vlb, 4, faulted=ops,
                              paced_cycles=2)
        else:
            got = _run_batch_sparse(dst, own0, vlb, 4)
            want = _host_loop(dst, own0, vlb, 4)
        assert np.asarray(got[0]).shape == (3, 4 * topo.num_slices)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_engine_dispatch_validates(self):
        from repro.netsim.fluid_jax import (
            SPARSE_AUTO_RACKS,
            resolve_engine,
        )

        assert resolve_engine("auto", SPARSE_AUTO_RACKS - 1) == "dense"
        assert resolve_engine("auto", SPARSE_AUTO_RACKS) == "sparse"
        assert resolve_engine("dense", 10_000) == "dense"
        assert resolve_engine("sparse", 8) == "sparse"
        with pytest.raises(ValueError):
            resolve_engine("turbo", 16)
