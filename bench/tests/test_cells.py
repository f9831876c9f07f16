"""Rehearsal of every driver at a tiny size on the CPU, through the same
`run` a chip run makes (the look for a TPU skipped), and the faults that
`correct` must catch, each planted under the timed path."""
import contextlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
TINY_NET = dict(
    name="tiny", k=4, num_racks=8, hosts_per_rack=2, num_circuit_switches=2,
    groups=1, link_rate_gbps=10.0, prop_delay_us=0.5, reconfig_delay_us=10.0,
    queue_bytes=24576, mtu=1500, topology="direct", topo_seed=0)
TINY = {
    "tiny-fluid": dict(
        config="opera-648", limits="fluid-648-bulk", net=TINY_NET,
        traffic=dict(driver="fluid", workloads=["shuffle", "permutation",
                                                "skew"],
                     loads=[0.1, 0.3], seeds_per_call=2, skew_frac=0.3,
                     max_cycles=6, vlb=True, batches=2, check_calls=2)),
    "tiny-flows": dict(
        config="opera-648", limits="flows-648-websearch", net=None,
        traffic=dict(driver="flows", workload="websearch",
                     loads=[0.01, 0.05, 0.1], horizon_s=0.04, tail_s=0.01,
                     dt_s=0.0002, batches=2, check_calls=1)),
}
SEED = 2**31 + 99


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout with the real yardstick and tiny cells beside the real
    ones: tiny configurations, traffic and the real cells' limits."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, t in TINY.items():
        cfg = json.loads((ROOT / f"bench/configs/{t['config']}.json")
                         .read_text())
        cfg.update(t["net"] or {})
        (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
        (root / f"bench/traffic/{name}.json").write_text(
            json.dumps(t["traffic"]))
        shutil.copy(ROOT / f"bench/limits/{t['limits']}.json",
                    root / f"bench/limits/{name}.json")
        spec["configs"].append(dict(name=name, source="test", reduced=[],
                                    file=f"bench/configs/{name}.json",
                                    why="test"))
        spec["workloads"].append(dict(name=name, config=name, traffic=name,
                                      chips=1, why="test"))
        real = "fluid-648-bulk" if "fluid" in name else "flows-648-websearch"
        for m in spec["end_to_end"] + spec["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(autouse=True)
def cpu_devices(monkeypatch):
    """The harness's look for a TPU skipped: the CPU stands in."""
    monkeypatch.setattr(R, "find_devices", lambda chips: jax.devices()[:chips])


def run_tiny(root, name, seconds=0.3):
    jax.clear_caches()
    return R.run(R.resolve(name, root), SEED, seconds, trace=False)


@pytest.fixture
def force_tiled(monkeypatch):
    from repro.netsim import flows_jax
    monkeypatch.setattr(flows_jax, "TILED_AUTO_FLOWS", 8)


@pytest.fixture
def force_sparse(monkeypatch):
    from repro.netsim import fluid_jax
    monkeypatch.setattr(fluid_jax, "SPARSE_AUTO_RACKS", 8)


def assert_sound(out, rate):
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {rate, "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["compiles_in_window"] == 0


@pytest.mark.parametrize("engine", ["dense", "sparse"])
def test_fluid_rehearsal(tiny_root, engine, request):
    if engine == "sparse":
        request.getfixturevalue("force_sparse")
    assert_sound(run_tiny(tiny_root, "tiny-fluid"), "fluid_slices_per_s")


@pytest.mark.parametrize("engine", ["dense", "tiled"])
def test_flows_rehearsal(tiny_root, engine, request):
    if engine == "tiled":
        request.getfixturevalue("force_tiled")
    assert_sound(run_tiny(tiny_root, "tiny-flows"), "flow_steps_per_s")


# --- faults planted under the timed path ---------------------------------

def _fluid_wrap(monkeypatch, fault):
    from repro.netsim import fluid_jax
    orig = fluid_jax.simulate_rotor_bulk_batch

    def broken(cfg, demands, **kw):
        if fault == "half_batch":
            half = demands[: len(demands) // 2]
            r = orig(cfg, np.concatenate([half, half]), **kw)
        else:
            r = orig(cfg, demands, **kw)
            t = r.finished_frac.shape[1] // 2
            r.finished_frac[0, t] += 1e-3        # one answer altered
        return r

    monkeypatch.setattr(fluid_jax, "simulate_rotor_bulk_batch", broken)


def _flows_wrap(monkeypatch, fault):
    from repro.netsim import flows_jax
    orig = flows_jax.simulate_flows_batch

    def broken(scenarios, **kw):
        if fault == "half_batch":
            n = len(scenarios)
            r = orig(scenarios[: (n + 1) // 2], **kw)
            for name in ("results", "remaining_bytes", "hists"):
                v = getattr(r, name)
                setattr(r, name, (v + v)[:n])
            return r
        r = orig(scenarios, **kw)
        r.results[-1].backlog_frac += 1e-3        # one answer altered
        return r

    monkeypatch.setattr(flows_jax, "simulate_flows_batch", broken)


def _state_unchanged(monkeypatch, kind):
    from repro.netsim import flows_jax, fluid_jax
    if kind == "fluid":
        monkeypatch.setattr(fluid_jax, "_slice_step",
                            lambda state, adj, vlb: (state, state[2:]))
    else:
        monkeypatch.setattr(flows_jax, "_tiled_step",
                            lambda carry, step, ops: carry)
        monkeypatch.setattr(
            flows_jax, "_flow_step",
            lambda carry, step, ops, trace: (carry, carry[0].sum()))


@pytest.mark.parametrize("cell", ["tiny-fluid", "tiny-flows"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
def test_planted_fault_is_not_correct(tiny_root, cell, fault, monkeypatch,
                                      force_tiled):
    kind = "fluid" if "fluid" in cell else "flows"
    if fault == "state_unchanged":
        _state_unchanged(monkeypatch, kind)
    else:
        (_fluid_wrap if kind == "fluid" else _flows_wrap)(monkeypatch, fault)
    with contextlib.ExitStack():
        out = run_tiny(tiny_root, cell, seconds=0.05)
    jax.clear_caches()
    assert out["correct"] is False, out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


# --- the harness refuses to measure without a chip ------------------------

def _bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fluid-648-bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    p = _bench(ROOT)
    assert p.returncode == 1 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _bench(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
