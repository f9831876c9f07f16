"""``python -m repro.staticcheck`` — run the full static-analysis suite.

Stages (select with ``--layers``):

* ``invariants`` — build the default Appendix-B design points and verify
  the four topology invariants, the static comparison fabrics, and the
  fault-mask artifact (SC-INV-FAULT, incl. each design's declared
  switch-fault budget).
* ``ast``        — walk every .py under src/tests/benchmarks/examples/
  scripts for the lockstep/trio/f64 policies.
* ``jaxpr``      — trace the thirteen engine entry points (dense +
  sparse + tiled-flow netsim engines plus their faulted lowerings, five
  Pallas kernels) and run the f64/callback/recompile rules.

Exit code 0 iff no ``error``-severity findings.  ``--json`` writes the
machine-readable report (CI keeps ``results/staticcheck.json``).
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Tuple

from repro.staticcheck.findings import Report

# Default Appendix-B design points: (k, num_racks, groups).  k12-n108-g1
# is the paper's 648-host §4 point; k12-n108-g2 exercises grouped
# reconfiguration; k8-n16-g1 is the small end of the App-B grid.
DEFAULT_DESIGNS: Tuple[Tuple[int, int, int], ...] = (
    (12, 108, 1),
    (12, 108, 2),
    (8, 16, 1),
)

# Declared per-slice switch-fault budgets per (k, num_racks, groups):
# SC-INV-FAULT proves every slice stays *fully* connected under every
# combination of up to this many failed circuit switches.  The paper's
# headline 2-of-6 tolerance (Fig. 11c) is a *cycle-level* property —
# a slice that loses 2 of its 5 live matchings can transiently fragment,
# while every pair still reaches every other in the surrounding slices
# and throughput retention stays >= 90% — and is verified dynamically by
# benchmarks/fig11_faults.py; the strict every-slice guarantee any
# k12-n108 realization attains is 1.  Designs not listed get budget 0 —
# SC-INV-FAULT still verifies their masked-tensor well-formedness, just
# no switch-combination sweep.
SWITCH_FAULT_BUDGETS = {(12, 108, 1): 1}


def _parse_designs(text: str) -> List[Tuple[int, int, int]]:
    out = []
    for part in text.split(","):
        k, n, g = (int(x.lstrip("kng")) for x in part.strip().split("-"))
        out.append((k, n, g))
    return out


def run_invariants(report: Report, designs, gap_frac: float) -> None:
    from repro.core.expander import random_regular_expander
    from repro.core.topology import build_opera_topology, expander_union
    from repro.staticcheck.invariants import (
        InvariantConfig,
        check_fault_masks,
        check_static_fabric,
        verify_topology,
    )

    def tag(found, k, n, g):
        for f in found:
            report.findings.append(type(f)(
                f.rule, f"[k{k}-n{n}-g{g}] {f.message}",
                path=f.path, line=f.line, severity=f.severity))

    cfg = InvariantConfig(gap_frac=gap_frac)
    for k, n, g in designs:
        topo = build_opera_topology(n, k // 2, seed=0, groups=g)
        tag(verify_topology(topo, config=cfg), k, n, g)
        report.checks_run.append(f"invariants:k{k}-n{n}-g{g}")
        budget = SWITCH_FAULT_BUDGETS.get((k, n, g), 0)
        tag(check_fault_masks(topo, budget=budget, config=cfg), k, n, g)
        report.checks_run.append(f"invariants:fault:k{k}-n{n}-g{g}")
    # static comparison fabrics (fig 2/4/7 baselines)
    report.extend(
        check_static_fabric(expander_union(130, 7, seed=0),
                            "expander_union(130, 7)", cfg),
        "invariants:expander_union",
    )
    report.extend(
        check_static_fabric(random_regular_expander(130, 7, seed=0),
                            "random_regular_expander(130, 7)", cfg),
        "invariants:random_regular_expander",
    )


def run_ast(report: Report, root: str, diff_base) -> None:
    from repro.staticcheck.ast_rules import scan_tree

    report.extend(scan_tree(root, diff_base=diff_base), "ast:tree")


def run_jaxpr(report: Report) -> None:
    from repro.staticcheck.jaxpr_rules import (
        check_callbacks,
        check_float64,
        count_fault_lowerings,
        count_sparse_lowerings,
        count_sweep_lowerings,
        count_tiled_lowerings,
        trace_entrypoints,
    )

    entries, trace_findings = trace_entrypoints()
    report.extend(trace_findings, "jaxpr:trace")
    report.extend(check_float64(entries), "jaxpr:float64")
    report.extend(check_callbacks(entries), "jaxpr:callbacks")
    _, _, recompile = count_sweep_lowerings()
    report.extend(recompile, "jaxpr:recompile")
    _, fault_recompile = count_fault_lowerings()
    report.extend(fault_recompile, "jaxpr:fault-recompile")
    _, sparse_recompile = count_sparse_lowerings()
    report.extend(sparse_recompile, "jaxpr:sparse-recompile")
    _, tiled_recompile = count_tiled_lowerings()
    report.extend(tiled_recompile, "jaxpr:tiled-recompile")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.staticcheck",
        description="Opera invariant verifier + jaxpr/AST static analysis",
    )
    ap.add_argument("--root", default=None,
                    help="repo root (default: auto from this file)")
    ap.add_argument("--layers", default="invariants,ast,jaxpr",
                    help="comma list of invariants,ast,jaxpr")
    ap.add_argument("--designs", default=None,
                    help="design points as k12-n108-g1,... "
                         "(default Appendix-B set)")
    ap.add_argument("--gap-frac", type=float, default=0.3,
                    help="required fraction of the Ramanujan-optimal "
                         "spectral gap (default 0.3)")
    ap.add_argument("--diff-base", default=None,
                    help="git rev to diff against for the lockstep rule "
                         "(default: working tree vs HEAD)")
    ap.add_argument("--json", default=None,
                    help="write machine-readable report to this path")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    root = args.root or os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", ".."))
    layers = [x.strip() for x in args.layers.split(",") if x.strip()]
    designs = (_parse_designs(args.designs) if args.designs
               else list(DEFAULT_DESIGNS))

    report = Report()
    if "invariants" in layers:
        run_invariants(report, designs, args.gap_frac)
    if "ast" in layers:
        run_ast(report, root, args.diff_base)
    if "jaxpr" in layers:
        run_jaxpr(report)

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        report.to_json(args.json)
    if not args.quiet:
        for f in report.findings:
            print(f)
        print(
            f"staticcheck: {len(report.checks_run)} checks, "
            f"{len(report.findings)} findings "
            f"({len(report.errors)} errors) -> "
            f"{'FAIL' if not report.ok else 'OK'}"
        )
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
