"""repro.staticcheck: invariant verifier + jaxpr/AST analyzer tests.

Each invariant rule is proven live by a seeded corrupted-topology
fixture that must fail it; the clean repo (and clean design points)
must pass everything — this is the tier-1 wiring of the analyzer.
"""
import dataclasses
import os
import textwrap

import numpy as np
import pytest

from repro.core.topology import build_opera_topology
from repro.staticcheck.findings import Finding, Report, allowed_lines
from repro.staticcheck.invariants import (
    InvariantConfig,
    check_cycle_coverage,
    check_expander,
    check_fault_masks,
    check_matching_union,
    check_reconfiguration,
    check_static_fabric,
    verify_topology,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    # small Appendix-B-style point: k=8 -> u=4, 16 racks, ungrouped
    return build_opera_topology(16, 4, seed=0, groups=1)


@pytest.fixture(scope="module")
def tensor(topo):
    return topo.matching_tensor()


def rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Layer 1: invariants — clean topologies pass
# ---------------------------------------------------------------------------


class TestInvariantsPass:
    def test_all_rules_clean_on_good_topology(self, topo, tensor):
        assert verify_topology(topo, tensor) == []

    @pytest.mark.parametrize("n,u,g", [(12, 3, 1), (16, 4, 2), (24, 4, 1)])
    def test_matching_cover_reconf_across_designs(self, n, u, g):
        t = build_opera_topology(n, u, seed=1, groups=g)
        ten = t.matching_tensor()
        assert check_matching_union(t, ten) == []
        assert check_cycle_coverage(t, ten) == []
        assert check_reconfiguration(t, ten) == []

    def test_static_fabrics_clean(self):
        from repro.core.expander import random_regular_expander
        from repro.core.topology import expander_union

        assert check_static_fabric(expander_union(26, 5, seed=0),
                                   "expander_union") == []
        assert check_static_fabric(random_regular_expander(26, 5, seed=0),
                                   "rre") == []


# ---------------------------------------------------------------------------
# Layer 1: invariants — seeded corrupted fixtures fail each rule
# ---------------------------------------------------------------------------


class TestCorruptedTopologies:
    def test_match_fails_on_self_map(self, topo, tensor):
        bad = tensor.copy()
        bad[0, 3, 3] = 1.0            # self-map in slice 0
        assert "SC-INV-MATCH" in rules(check_matching_union(topo, bad))

    def test_match_fails_on_asymmetric_edge(self, topo, tensor):
        bad = tensor.copy()
        n = topo.num_racks
        off_zero = np.argwhere((bad[0] == 0) & ~np.eye(n, dtype=bool))
        i, j = off_zero[0]
        bad[0, i, j] = 1.0            # directed edge with no reverse
        assert "SC-INV-MATCH" in rules(check_matching_union(topo, bad))

    def test_match_fails_on_non_involution_matching(self, topo):
        # replace one switch matching by a 3-cycle permutation (a valid
        # permutation but NOT an involution -> not a matching)
        n = topo.num_racks
        cyc = np.roll(np.arange(n), 1).astype(np.int64)
        sm = [list(ms) for ms in topo.switch_matchings]
        sm[0][0] = cyc
        bad = dataclasses.replace(
            topo, switch_matchings=tuple(tuple(ms) for ms in sm))
        assert "SC-INV-MATCH" in rules(check_matching_union(bad))

    def test_cover_fails_on_dropped_pair(self, topo, tensor):
        bad = tensor.copy()
        bad[:, 0, 1] = 0.0            # pair (0, 1) never gets a circuit
        bad[:, 1, 0] = 0.0
        found = check_cycle_coverage(topo, bad)
        assert "SC-INV-COVER" in rules(found)
        assert any("no direct circuit" in f.message for f in found)

    def test_cover_fails_on_duplicated_slice_coverage(self, topo, tensor):
        bad = tensor.copy()
        bad[1] = bad[0]               # double-covers slice 0's pairs
        assert "SC-INV-COVER" in rules(check_cycle_coverage(topo, bad))

    def test_expander_fails_on_disconnected_slice(self, topo, tensor):
        n = topo.num_racks
        half = n // 2
        blk = np.zeros((n, n), np.float32)
        blk[:half, :half] = 1.0       # two cliques, no bridge
        blk[half:, half:] = 1.0
        np.fill_diagonal(blk, 0.0)
        bad = tensor.copy()
        bad[2] = blk
        found = check_expander(topo, bad)
        assert "SC-INV-EXPAND" in rules(found)
        assert any("disconnected" in f.message for f in found)

    def test_expander_fails_on_low_spectral_gap(self, topo, tensor):
        # barbell: two cliques joined by one edge — connected, min degree
        # 7, but a near-zero spectral gap (the classic bad expander)
        n = topo.num_racks
        half = n // 2
        barbell = np.zeros((n, n), np.float32)
        barbell[:half, :half] = 1.0
        barbell[half:, half:] = 1.0
        np.fill_diagonal(barbell, 0.0)
        barbell[0, half] = barbell[half, 0] = 1.0
        bad = tensor.copy()
        bad[1] = barbell
        found = check_expander(topo, bad)
        assert "SC-INV-EXPAND" in rules(found)
        assert any("spectral gap" in f.message for f in found)

    def test_reconf_fails_on_wholesale_slice_swap(self, topo, tensor):
        # relabel one slice by a seeded random permutation: nearly every
        # live link moves -> way beyond the 2*groups*N piecewise bound
        rng = np.random.default_rng(7)
        perm = rng.permutation(topo.num_racks)
        bad = tensor.copy()
        bad[1] = bad[1][perm][:, perm]
        assert "SC-INV-RECONF" in rules(check_reconfiguration(topo, bad))

    def test_fabric_fails_on_disconnected(self):
        adj = np.zeros((8, 8), bool)
        adj[:4, :4] = ~np.eye(4, dtype=bool)
        adj[4:, 4:] = ~np.eye(4, dtype=bool)
        assert "SC-INV-FABRIC" in rules(check_static_fabric(adj, "split"))


# ---------------------------------------------------------------------------
# Layer 1: SC-INV-FAULT — fault-masked tensors + switch-fault budget
# ---------------------------------------------------------------------------


class TestFaultInvariant:
    def test_clean_on_budget_selected_realization(self):
        # n12-u6 converges instantly in the generate-and-test loop and
        # genuinely survives any 2 switch failures in every slice
        ft = build_opera_topology(12, 6, seed=0, switch_fault_tolerance=2)
        assert check_fault_masks(ft, budget=2) == []

    def test_fires_on_unselected_realization(self):
        # plain 16-rack u=4 seed-0 build: single-switch failures leave
        # 2-matching slices that fall apart into disjoint cycles
        topo = build_opera_topology(16, 4, seed=0)
        found = check_fault_masks(topo, budget=1)
        assert "SC-INV-FAULT" in rules(found)
        assert any("disconnects under switch failures" in f.message
                   for f in found)

    def test_fires_on_asymmetric_masked_tensor(self, topo, tensor):
        bad = tensor.copy()
        n = topo.num_racks
        off_zero = np.argwhere((bad[0] == 0) & ~np.eye(n, dtype=bool))
        i, j = off_zero[0]
        bad[0, i, j] = 1.0            # survives masking -> masked asym
        found = check_fault_masks(topo, tensor=bad)
        assert any("not symmetric" in f.message for f in found)

    def test_fires_when_draw_removes_nothing(self, topo):
        # an all-zero tensor has no capacity for the link draw to remove
        zero = np.zeros_like(topo.matching_tensor())
        found = check_fault_masks(topo, tensor=zero)
        assert any("removed no" in f.message for f in found)


# ---------------------------------------------------------------------------
# Layer 2b: AST rules
# ---------------------------------------------------------------------------


def _scan_src(tmp_path, rel, source):
    """Write `source` at tmp_path/rel and run the per-file AST rules."""
    import ast as ast_mod

    from repro.staticcheck.ast_rules import check_engine_f64

    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    tree = ast_mod.parse(path.read_text())
    root = str(tmp_path)
    return check_engine_f64(root, str(path), tree, path.read_text())


class TestAstRules:
    def test_engine_f64_requires_directive(self, tmp_path):
        src = """\
            import numpy as np
            a = np.zeros(3, np.float64)
            b = np.zeros(3, np.float64)  # staticcheck: ok SC-AST-F64 (host staging)
            # staticcheck: ok SC-AST-F64 (host staging)
            c = np.zeros(3, np.float64)
            """
        found = _scan_src(tmp_path, "src/repro/netsim/foo_jax.py", src)
        assert [f.rule for f in found] == ["SC-AST-F64"]
        assert found[0].line == 2
        # same file outside an engine path: rule does not apply
        assert _scan_src(tmp_path, "src/repro/netsim/foo.py", src) == []

    def test_directive_parser(self):
        src = "x = 1\n# staticcheck: ok SC-AST-F64, SC-JAX-F64 (why)\ny = 2\n"
        ok = allowed_lines(src, "SC-AST-F64")
        assert ok == {2, 3}
        assert allowed_lines(src, "SC-INV-MATCH") == set()

    def test_kernel_trio_missing_ref_flagged(self, tmp_path):
        from repro.staticcheck.ast_rules import check_kernel_trios

        pkg = tmp_path / "src" / "repro" / "kernels" / "newkern"
        pkg.mkdir(parents=True)
        (pkg / "kernel.py").write_text("")
        (pkg / "ops.py").write_text("")
        found = check_kernel_trios(str(tmp_path))
        assert rules(found) == {"SC-AST-TRIO"}
        assert "ref.py" in found[0].message

    def test_lockstep_pair_rule(self):
        from repro.staticcheck.ast_rules import check_lockstep

        lone = check_lockstep(["src/repro/netsim/fluid_jax.py"])
        assert rules(lone) == {"SC-AST-LOCKSTEP"}
        both = check_lockstep(["src/repro/netsim/fluid.py",
                               "src/repro/netsim/fluid_jax.py",
                               "src/repro/netsim/flows.py",
                               "src/repro/netsim/flows_jax.py"])
        assert both == []
        unrelated = check_lockstep(["ROADMAP.md", "src/repro/launch/mesh.py"])
        assert unrelated == []

    def test_lockstep_faults_coupling(self):
        """A faults.py diff is a failure-semantics diff: every engine
        pair must be touched (both members), else the pair is flagged."""
        from repro.staticcheck.ast_rules import check_lockstep

        alone = check_lockstep(["src/repro/netsim/faults.py"])
        assert len(alone) == 2          # one finding per untouched pair
        assert rules(alone) == {"SC-AST-LOCKSTEP"}
        assert all("failure semantics" in f.message for f in alone)
        half = check_lockstep(["src/repro/netsim/faults.py",
                               "src/repro/netsim/fluid.py"])
        # fluid pair: half-touched (base rule); flows pair: untouched
        assert len(half) == 2
        full = check_lockstep(["src/repro/netsim/faults.py",
                               "src/repro/netsim/fluid.py",
                               "src/repro/netsim/fluid_jax.py",
                               "src/repro/netsim/flows.py",
                               "src/repro/netsim/flows_jax.py"])
        assert full == []

    def test_whole_tree_is_clean(self):
        """Tier-1 gate: the repo itself passes every AST policy rule."""
        from repro.staticcheck.ast_rules import scan_tree

        found = scan_tree(REPO_ROOT, lockstep=False)
        assert found == [], "\n".join(str(f) for f in found)


# ---------------------------------------------------------------------------
# Layer 2a: jaxpr rules
# ---------------------------------------------------------------------------


class TestJaxprRules:
    @pytest.fixture(scope="class")
    def entries(self):
        from repro.staticcheck.jaxpr_rules import trace_entrypoints

        entries, trace_findings = trace_entrypoints()
        assert trace_findings == []
        return entries

    def test_all_entrypoints_trace(self, entries):
        names = {e.name for e in entries}
        assert len(names) == 13
        assert any("fluid_jax" in n for n in names)
        for program in ("fluid_jax._run_batch", "fluid_jax._run_batch_sparse",
                        "flows_jax._run_batch", "flows_jax._run_tiled_chunk"):
            assert f"netsim.{program}" in names
            assert f"netsim.{program}[faulted]" in names
        assert "kernels.rotor_slice.ops.rotor_slice_step" in names
        assert any("flash_attention" in n for n in names)

    def test_engines_have_no_f64_or_callbacks(self, entries):
        from repro.staticcheck.jaxpr_rules import check_callbacks, check_float64

        assert check_float64(entries) == []
        assert check_callbacks(entries) == []

    def test_f64_leak_is_caught(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.staticcheck.jaxpr_rules import TracedEntry, check_float64

        def leaky(x):
            return x * jnp.asarray(np.float64(2.0))  # f64 constant promotes

        with jax.enable_x64(True):
            closed = jax.make_jaxpr(leaky)(
                jax.ShapeDtypeStruct((4,), jnp.float32))
        found = check_float64([TracedEntry("leaky", "x.py", 1, closed)])
        assert rules(found) == {"SC-JAX-F64"}

    def test_host_callback_is_caught(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.staticcheck.jaxpr_rules import TracedEntry, check_callbacks

        def chatty(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a), jax.ShapeDtypeStruct((4,), jnp.float32), x)
            return jax.lax.scan(lambda c, _: (c + y, None), x, None, length=3)[0]

        closed = jax.make_jaxpr(chatty)(jax.ShapeDtypeStruct((4,), jnp.float32))
        found = check_callbacks([TracedEntry("chatty", "x.py", 1, closed)])
        assert rules(found) == {"SC-JAX-CALLBACK"}


class TestRecompilePinning:
    def test_sweep_grid_compiles_once_per_design_point(self):
        """Regression pin (ROADMAP sweep runner): a (k, num_racks, groups)
        x workload x load x seed grid must produce exactly one fresh
        `fluid_jax._run_batch` lowering per design point, and re-running
        the same grid with different loads/seeds must reuse them all."""
        from repro.staticcheck.jaxpr_rules import count_sweep_lowerings

        designs = ((4, 14, 1), (4, 18, 1))   # shapes unique to this test
        new, num_designs, findings = count_sweep_lowerings(
            designs=designs, loads=(0.1, 0.25), seeds=(0, 1), max_cycles=8)
        assert findings == []
        assert new == num_designs == len(designs)
        # same design shapes, fresh loads/seeds: zero new lowerings
        new2, _, findings2 = count_sweep_lowerings(
            designs=designs, loads=(0.15, 0.3), seeds=(2, 3), max_cycles=8)
        assert findings2 == []
        assert new2 == 0

    def test_fault_draws_share_one_lowering(self):
        """Failure timelines are data: distinct draws through one design
        point must add at most one faulted `_run_batch` lowering, and a
        re-run with fresh draws must add none."""
        from repro.staticcheck.jaxpr_rules import count_fault_lowerings

        new, findings = count_fault_lowerings(num_draws=3, max_cycles=5)
        assert findings == []
        assert new <= 1
        new2, findings2 = count_fault_lowerings(num_draws=2, max_cycles=5)
        assert findings2 == []
        assert new2 == 0

    def test_sparse_demand_draws_share_one_lowering(self):
        """Sparse engine: distinct demand draws through one design point
        must add at most one `_run_batch_sparse` lowering (the index
        tensor is data, not static, and no program is lowered per
        slice), and a re-run must add none; a new `max_cycles` is a new
        static shape and adds exactly one."""
        from repro.staticcheck.jaxpr_rules import count_sparse_lowerings

        new, findings = count_sparse_lowerings(num_cycles=3, num_demands=2)
        assert findings == []
        assert new <= 1
        new2, findings2 = count_sparse_lowerings(num_cycles=3, num_demands=2)
        assert findings2 == []
        assert new2 == 0
        new3, findings3 = count_sparse_lowerings(num_cycles=5, num_demands=2)
        assert findings3 == []
        assert new3 == 1

    def test_tiled_flow_grid_shares_one_lowering(self):
        """Tiled flow engine: chunk shapes are (batch, window, tile)
        geometry only — loads and seeds are data.  A load x seed grid
        must add at most one `_run_tiled_chunk` lowering across a cold
        run plus a warm re-run, and a further re-run must add none."""
        from repro.staticcheck.jaxpr_rules import count_tiled_lowerings

        new, findings = count_tiled_lowerings(loads=(0.05, 0.2),
                                              seeds=(0, 1))
        assert findings == []
        assert new <= 1
        new2, findings2 = count_tiled_lowerings(loads=(0.1, 0.15),
                                                seeds=(2, 3))
        assert findings2 == []
        assert new2 == 0


def _fluid_runner(engine):
    from repro.netsim import fluid_jax
    from repro.netsim.faults import FailureSchedule
    from repro.netsim.sweep import DesignPoint

    fab = build_opera_topology(8, 2, seed=0)
    cfg = DesignPoint(k=4, num_racks=8).to_config()
    demand = np.full((1, 8, 8), 1e6)
    demand[0][np.diag_indices(8)] = 0.0
    program = (fluid_jax._run_batch_sparse if engine == "sparse"
               else fluid_jax._run_batch)

    def run(draw):
        faults = None if draw is None else FailureSchedule.draw(
            fab, seed=draw, link_frac=0.1, switch_count=1, onset_step=2)
        fluid_jax.simulate_rotor_bulk_batch(
            cfg, demand, topo=fab, max_cycles=3, faults=faults,
            engine=engine)
    return program, run


def _flows_runner(engine):
    from repro.netsim import flows_jax
    from repro.netsim.faults import FailureSchedule, apply_flow_faults
    from repro.netsim.flows import build_scenario

    fab = build_opera_topology(8, 2, seed=0)
    scn = build_scenario("opera", "websearch", 0.12, seed=0, num_hosts=16,
                         horizon_s=0.06, dt_s=5e-4, tail_s=0.04)
    program = (flows_jax._run_tiled_chunk if engine == "tiled"
               else flows_jax._run_batch)

    def run(draw):
        batch = scn if draw is None else apply_flow_faults(
            scn, FailureSchedule.draw(fab, seed=draw, tor_frac=0.25,
                                      onset_step=20, detect_lag=5,
                                      recover_step=80))
        flows_jax.simulate_flows_batch(
            [batch], engine=engine, tile_size=64, window_tiles=16,
            chunk_steps=32)
    return program, run


@pytest.mark.parametrize("runner,engine", [
    (_fluid_runner, "dense"), (_fluid_runner, "sparse"),
    (_flows_runner, "dense"), (_flows_runner, "tiled"),
], ids=["fluid-dense", "fluid-sparse", "flows-dense", "flows-tiled"])
def test_fault_operand_adds_one_lowering(runner, engine):
    """Each engine path is one jitted program whose fault operand is an
    optional pytree: a clean call lowers it once, two distinct failure
    draws add exactly one faulted lowering between them, and repeating
    all three calls adds none."""
    program, run = runner(engine)
    program._clear_cache()
    run(None)
    assert program._cache_size() == 1
    run(0)
    run(1)
    assert program._cache_size() == 2
    for draw in (None, 0, 1):
        run(draw)
    assert program._cache_size() == 2


# ---------------------------------------------------------------------------
# Report plumbing + CLI smoke
# ---------------------------------------------------------------------------


class TestReport:
    def test_report_json_roundtrip(self, tmp_path):
        import json

        rep = Report()
        rep.extend([Finding("SC-INV-COVER", "boom", path="cycle-union"),
                    Finding("SC-AST-LOCKSTEP", "warn", path="a.py",
                            severity="warning")], "unit")
        assert not rep.ok
        assert rep.by_rule() == {"SC-INV-COVER": 1, "SC-AST-LOCKSTEP": 1}
        p = tmp_path / "report.json"
        rep.to_json(str(p))
        data = json.loads(p.read_text())
        assert data["num_errors"] == 1 and data["ok"] is False
        assert data["findings"][0]["rule"] == "SC-INV-COVER"

    def test_cli_small_design_exits_zero(self, tmp_path, capsys):
        from repro.staticcheck.cli import main

        out = tmp_path / "sc.json"
        rc = main(["--layers", "invariants,ast", "--designs", "k8-n16-g1",
                   "--json", str(out), "--root", REPO_ROOT, "-q"])
        assert rc == 0
        assert out.exists()
