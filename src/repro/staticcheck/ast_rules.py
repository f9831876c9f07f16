"""Layer 2b — AST rules enforcing repo code policies (ROADMAP notes).

SC-AST-F64       float32 device-engine modules (``netsim/*_jax.py``) may
                 touch float64 only on explicitly annotated host-side
                 staging lines (``# staticcheck: ok SC-AST-F64 (...)``).
SC-AST-TRIO      every kernel package under ``kernels/`` ships the full
                 ``kernel.py`` / ``ops.py`` / ``ref.py`` trio.
SC-AST-LOCKSTEP  oracle<->JAX engine pairs must change together in a
                 diff (``git diff --name-only``): fluid.py<->fluid_jax.py,
                 flows.py<->flows_jax.py.  A diff touching
                 ``netsim/faults.py`` carries failure *semantics* (the
                 per-step mask/window math both members of each pair
                 mirror), so it must touch both members of each pair
                 too — or neither gets a pass: an untouched pair under a
                 faults.py diff is flagged for review.
"""
from __future__ import annotations

import ast
import fnmatch
import os
import subprocess
from typing import Iterable, List, Optional, Sequence, Tuple

from repro.staticcheck.findings import Finding, WARNING, allowed_lines

SCAN_DIRS = ("src", "tests", "benchmarks", "examples", "scripts")
ENGINE_F64_GLOBS = ("*/netsim/*_jax.py",)
LOCKSTEP_PAIRS: Tuple[Tuple[str, str], ...] = (
    ("src/repro/netsim/fluid.py", "src/repro/netsim/fluid_jax.py"),
    ("src/repro/netsim/flows.py", "src/repro/netsim/flows_jax.py"),
)
# failure-semantics module: its per-step mask/window math is mirrored
# inside every member of LOCKSTEP_PAIRS (faults.step_masks <->
# fluid_jax._slice_masks, apply_flow_faults windows <-> both flow
# engines' step bodies), so a diff touching it couples to every pair
FAULTS_MODULE = "src/repro/netsim/faults.py"


def iter_py_files(root: str, dirs: Sequence[str] = SCAN_DIRS) -> Iterable[str]:
    for d in dirs:
        base = os.path.join(root, d)
        if not os.path.isdir(base):
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [x for x in dirnames if x != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def _rel(root: str, path: str) -> str:
    return os.path.relpath(path, root)


def check_engine_f64(root: str, path: str, tree: ast.AST,
                     source: str) -> List[Finding]:
    """SC-AST-F64 on one parsed module (engine modules only)."""
    rel = _rel(root, path).replace(os.sep, "/")
    if not any(fnmatch.fnmatch(rel, g) for g in ENGINE_F64_GLOBS):
        return []
    ok = allowed_lines(source, "SC-AST-F64")
    out: List[Finding] = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and node.attr == "float64"
                and node.lineno not in ok):
            out.append(Finding(
                "SC-AST-F64",
                "float64 in a float32 device engine — move it to annotated "
                "host-side staging (`# staticcheck: ok SC-AST-F64 (...)`) "
                "or drop it",
                path=_rel(root, path), line=node.lineno))
    return out


def check_kernel_trios(root: str) -> List[Finding]:
    """SC-AST-TRIO over src/repro/kernels/*."""
    out: List[Finding] = []
    base = os.path.join(root, "src", "repro", "kernels")
    if not os.path.isdir(base):
        return out
    for name in sorted(os.listdir(base)):
        pkg = os.path.join(base, name)
        if not os.path.isdir(pkg) or name == "__pycache__":
            continue
        missing = [f for f in ("kernel.py", "ops.py", "ref.py")
                   if not os.path.exists(os.path.join(pkg, f))]
        if missing:
            out.append(Finding(
                "SC-AST-TRIO",
                f"kernel package `{name}` missing {', '.join(missing)} "
                "(kernel/ops/ref trio is mandatory)",
                path=_rel(root, pkg)))
    return out


def git_changed_files(root: str, base: Optional[str] = None) -> List[str]:
    """Changed files vs `base` (or the working tree vs HEAD)."""
    cmd = ["git", "diff", "--name-only"] + ([base] if base else ["HEAD"])
    try:
        res = subprocess.run(cmd, cwd=root, capture_output=True, text=True,
                             check=True)
    except (OSError, subprocess.CalledProcessError):
        return []
    return [ln.strip() for ln in res.stdout.splitlines() if ln.strip()]


def check_lockstep(changed_files: Sequence[str]) -> List[Finding]:
    """SC-AST-LOCKSTEP over a diff file list."""
    changed = {f.replace(os.sep, "/") for f in changed_files}
    out: List[Finding] = []
    faulted = FAULTS_MODULE in changed
    for a, b in LOCKSTEP_PAIRS:
        in_a, in_b = a in changed, b in changed
        if in_a != in_b:
            lone, partner = (a, b) if in_a else (b, a)
            out.append(Finding(
                "SC-AST-LOCKSTEP",
                f"{lone} changed without its lockstep partner {partner} — "
                "oracle and JAX engine share per-step math; change them "
                "together (ROADMAP Architecture notes)",
                path=lone, severity=WARNING))
        elif faulted and not in_a:
            out.append(Finding(
                "SC-AST-LOCKSTEP",
                f"{FAULTS_MODULE} changed but neither {a} nor {b} did — "
                "failure semantics (per-step masks / fault windows) are "
                "mirrored inside both engines; touch both pair members "
                "or confirm the diff is schedule-plumbing only",
                path=FAULTS_MODULE, severity=WARNING))
    return out


def scan_tree(root: str, diff_base: Optional[str] = None,
              lockstep: bool = True) -> List[Finding]:
    """All AST rules over the repo tree."""
    out: List[Finding] = []
    for path in iter_py_files(root):
        with open(path, "r") as fh:
            source = fh.read()
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            out.append(Finding("SC-AST-PARSE", f"syntax error: {e}",
                               path=_rel(root, path), line=e.lineno))
            continue
        out += check_engine_f64(root, path, tree, source)
    out += check_kernel_trios(root)
    if lockstep:
        out += check_lockstep(git_changed_files(root, diff_base))
    return out
