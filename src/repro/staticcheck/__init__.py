"""Static-analysis suite for the Opera reproduction.

Two layers, one finding vocabulary (`Finding`, rule IDs `SC-*`):

* **Artifact verifier** (`staticcheck.invariants`) — proves the structural
  invariants Opera's correctness argument rests on (PAPER.md §3) directly
  from design-time artifacts, without simulating: every slice of
  `OperaTopology.matching_tensor()` is a disjoint union of involutive
  matchings with no self-maps, one cycle gives exact single-hop coverage
  of every ordered rack pair, every slice graph is a connected expander,
  and consecutive slices differ by at most the reconfiguring groups'
  matchings.
* **Code analyzer** (`staticcheck.jaxpr_rules`, `staticcheck.ast_rules`)
  — traces the jitted engine entry points to closed jaxprs and flags
  float64 leaks / host callbacks / sweep-grid recompilation, and walks
  the tree's ASTs to enforce the repo policies from ROADMAP Architecture
  notes (oracle<->JAX lockstep pairs, kernel trio completeness,
  annotated host-side float64 staging).

Run it: ``python -m repro.staticcheck`` (CLI, exits non-zero on
violations, writes ``results/staticcheck.json``) or via
``tests/test_staticcheck.py`` in tier-1.  Per-line allowlisting uses a
directive comment: ``# staticcheck: ok SC-AST-F64 (reason)`` on the
flagged line or the line above it.
"""
from repro.staticcheck.findings import Finding, Report

__all__ = ["Finding", "Report"]
