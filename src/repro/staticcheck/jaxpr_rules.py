"""Layer 2a — jaxpr rules over the jitted engine entry points.

Each engine's device program is traced (abstractly — nothing executes)
to a closed jaxpr under ``jax_enable_x64`` so dtype leaks that silent
x64-off demotion would mask become visible, then walked recursively
(scan/cond/pjit/pallas_call sub-jaxprs included):

SC-JAX-F64        a float64 value materializes inside a float32 engine —
                  a weak-type or literal promotion that doubles memory
                  traffic and silently de-synchronizes the f32 oracle
                  lockstep.
SC-JAX-CALLBACK   a host callback primitive (pure_callback/io_callback/
                  debug_callback/outside_call) inside a hot loop —
                  forces a device->host sync every step.
SC-JAX-RECOMPILE  the sweep grid compiles more than once per design
                  point: `netsim/sweep.py` must reuse one lowering of
                  `fluid_jax._run_batch` per (k, num_racks, groups)
                  shape, never one per load/seed scenario.  The fault
                  path has the same contract (`count_fault_lowerings`):
                  failure timelines are int32 *data* operands of
                  `_run_batch_faulted`, so distinct failure draws must
                  never trigger fresh lowerings.

Traced entry points: ``fluid_jax._run_batch`` / ``_run_batch_faulted``
(the dense device programs under ``simulate_rotor_bulk_batch``),
``fluid_jax._run_batch_sparse`` / ``_run_batch_sparse_faulted`` (the
sparse engine's slice-loop programs, paced demand included —
``count_sparse_lowerings`` holds the clean one to one lowering per
(design point, ``vlb``, ``max_cycles``), whatever the demand draw),
``flows_jax._run_batch`` / ``_run_batch_faulted`` (under
``simulate_grid`` / ``simulate_flows_batch``),
``flows_jax._run_tiled_chunk`` / ``_run_tiled_chunk_faulted`` (the
streaming tiled flow engine's chunk programs — shapes depend on the
(batch, window_tiles, tile) geometry only, never on the scenario's
flow count, and ``count_tiled_lowerings`` holds them to one lowering
per design point across loads and seeds), and the five Pallas kernel
``ops`` wrappers (``rotor_slice_step`` traced with
``force_pallas=True`` so the kernel body, not the CPU ref fast path,
is what the rules walk).
"""
from __future__ import annotations

import dataclasses
import inspect
import os
from typing import Callable, List, Optional, Sequence, Tuple

from repro.staticcheck.findings import Finding

CALLBACK_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "outside_call",
    "host_callback_call", "infeed", "outfeed",
}


@dataclasses.dataclass
class TracedEntry:
    name: str
    path: str          # repo-relative module path
    line: int
    jaxpr: object      # jax.extend.core.ClosedJaxpr


def _src_location(fn) -> Tuple[str, int]:
    try:
        path = inspect.getsourcefile(fn) or "<unknown>"
        line = inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        return "<unknown>", 0
    marker = os.sep + "repro" + os.sep
    if marker in path:
        path = "src" + os.sep + "repro" + os.sep + path.split(marker, 1)[1]
    return path.replace(os.sep, "/"), line


def _entry_specs() -> List[Tuple[str, Callable, Callable]]:
    """(name, traced_callable, args_builder) for every engine entry point.

    Imports live inside so the AST layer stays importable without jax.
    """
    import jax
    import jax.numpy as jnp

    def sd(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)

    from repro.netsim import flows_jax, fluid_jax
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.mamba_scan.ops import mamba_scan
    from repro.kernels.moe_gmm.ops import moe_gmm
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rotor_slice.ops import rotor_slice_step

    return [
        (
            "netsim.fluid_jax._run_batch",
            lambda a, o: fluid_jax._run_batch(a, o, True, 3),
            lambda: (sd((6, 8, 8)), sd((2, 8, 8))),
        ),
        (
            "netsim.fluid_jax._run_batch_sparse",
            lambda d, o: fluid_jax._run_batch_sparse(d, o, True, 3),
            lambda: (sd((6, 8, 2), jnp.int32), sd((2, 8, 8))),
        ),
        (
            "netsim.fluid_jax._run_batch_sparse_faulted",
            lambda *a: fluid_jax._run_batch_sparse_faulted(*a, True, 3, 2),
            lambda: (
                sd((6, 8, 2), jnp.int32), sd((8, 8), jnp.int32),
                sd((2, 8, 8)),
                sd((2, 8, 3), jnp.int32), sd((2, 8, 3), jnp.int32),
                sd((2, 8, 3), jnp.int32),
                sd((2, 8), jnp.int32), sd((2, 8), jnp.int32),
                sd((2, 8), jnp.int32),
            ),
        ),
        (
            "netsim.fluid_jax._run_batch_faulted",
            lambda *a: fluid_jax._run_batch_faulted(*a, True, 3, 0),
            lambda: (
                sd((6, 8, 8)), sd((6, 8, 8), jnp.int32),
                sd((8, 8), jnp.int32), sd((2, 8, 8)),
                sd((2, 8, 3), jnp.int32), sd((2, 8, 3), jnp.int32),
                sd((2, 8, 3), jnp.int32),
                sd((2, 8), jnp.int32), sd((2, 8), jnp.int32),
                sd((2, 8), jnp.int32),
            ),
        ),
        (
            "netsim.flows_jax._run_batch",
            lambda *a: flows_jax._run_batch(*a, num_steps=7, trace=False),
            lambda: (
                sd((2, 5)), sd((2, 5), jnp.int32), sd((2, 5), jnp.bool_),
                sd((2,)), sd((2,)), sd((2, 5)), sd((2, 5)),
                sd((2,), jnp.int32), sd((2,), jnp.int32),
                sd((2, 5), jnp.int32), sd((2, 5)), sd((2,)),
            ),
        ),
        (
            "netsim.flows_jax._run_batch_faulted",
            lambda *a: flows_jax._run_batch_faulted(*a, num_steps=7,
                                                    trace=False),
            lambda: (
                sd((2, 5)), sd((2, 5), jnp.int32), sd((2, 5), jnp.bool_),
                sd((2,)), sd((2,)), sd((2, 5)), sd((2, 5)),
                sd((2,), jnp.int32), sd((2,), jnp.int32),
                sd((2, 5), jnp.int32), sd((2, 5)), sd((2,)),
                sd((2, 5), jnp.int32), sd((2, 5), jnp.int32),
                sd((2, 5), jnp.int32), sd((2, 5), jnp.int32),
                sd((2, 7)), sd((2, 7)),
            ),
        ),
        (
            "netsim.flows_jax._run_tiled_chunk",
            lambda *a: flows_jax._run_tiled_chunk(*a, num_steps=7,
                                                  chunk_steps=4),
            lambda: (
                sd((2, 3, 4)), sd((2, 3, 4)), sd((2, 3, 4), jnp.int32),
                sd((2, 3, 4), jnp.bool_), sd((2, 3, 4), jnp.int32),
                sd((2, 3, 4)),
                sd((2,)), sd((2,)), sd((2,)),
                sd((2,), jnp.int32), sd((2,), jnp.int32),
                sd((2, 288), jnp.int32), sd((2,)), sd((2,)), sd((2,)),
                sd((), jnp.int32),
            ),
        ),
        (
            "netsim.flows_jax._run_tiled_chunk_faulted",
            lambda *a: flows_jax._run_tiled_chunk_faulted(*a, num_steps=7,
                                                          chunk_steps=4),
            lambda: (
                sd((2, 3, 4)), sd((2, 3, 4)), sd((2, 3, 4), jnp.int32),
                sd((2, 3, 4), jnp.bool_), sd((2, 3, 4), jnp.int32),
                sd((2, 3, 4)),
                sd((2,)), sd((2,)), sd((2,)),
                sd((2,), jnp.int32), sd((2,), jnp.int32),
                sd((2, 3, 4), jnp.int32), sd((2, 3, 4), jnp.int32),
                sd((2, 3, 4), jnp.int32), sd((2, 3, 4), jnp.int32),
                sd((2, 4)), sd((2, 4)),
                sd((2, 288), jnp.int32), sd((2,)), sd((2,)), sd((2,)),
                sd((), jnp.int32),
            ),
        ),
        (
            "kernels.flash_attention.ops.flash_attention",
            lambda q, k, v: flash_attention(q, k, v, interpret=True),
            lambda: (sd((1, 2, 16, 8)), sd((1, 2, 16, 8)), sd((1, 2, 16, 8))),
        ),
        (
            "kernels.mamba_scan.ops.mamba_scan",
            lambda x, dt, B, C, A, D: mamba_scan(x, dt, B, C, A, D,
                                                 interpret=True),
            lambda: (sd((1, 8, 16)), sd((1, 8, 16)), sd((1, 8, 4)),
                     sd((1, 8, 4)), sd((16, 4)), sd((16,))),
        ),
        (
            "kernels.moe_gmm.ops.moe_gmm",
            lambda h, wg, wu, wd: moe_gmm(h, wg, wu, wd, interpret=True),
            lambda: (sd((2, 8, 16)), sd((2, 16, 32)), sd((2, 16, 32)),
                     sd((2, 32, 16))),
        ),
        (
            "kernels.rglru_scan.ops.rglru_scan",
            lambda a, bx, h0: rglru_scan(a, bx, h0, interpret=True),
            lambda: (sd((1, 8, 16)), sd((1, 8, 16)), sd((1, 16))),
        ),
        (
            "kernels.rotor_slice.ops.rotor_slice_step",
            lambda o, r, d: rotor_slice_step(o, r, d, interpret=True,
                                             force_pallas=True),
            lambda: (sd((2, 8, 8)), sd((2, 8, 8)), sd((8, 2), jnp.int32)),
        ),
    ]


def trace_entrypoints(
    only: Optional[Sequence[str]] = None,
) -> Tuple[List[TracedEntry], List[Finding]]:
    """Abstractly trace every engine entry point under enable_x64."""
    import jax

    entries: List[TracedEntry] = []
    findings: List[Finding] = []
    with jax.enable_x64(True):
        for name, fn, build_args in _entry_specs():
            if only and not any(o in name for o in only):
                continue
            path, line = _src_location(fn)
            try:
                closed = jax.make_jaxpr(fn)(*build_args())
            except Exception as e:  # a broken trace is itself a finding
                findings.append(Finding(
                    "SC-JAX-TRACE", f"{name} failed to trace: {e!r}",
                    path=path, line=line))
                continue
            entries.append(TracedEntry(name, path, line, closed))
    return entries, findings


def _walk_jaxpr(jaxpr, visit) -> None:
    """Depth-first over eqns, recursing into any sub-jaxpr params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def maybe_recurse(v):
        if isinstance(v, ClosedJaxpr):
            _walk_jaxpr(v.jaxpr, visit)
        elif isinstance(v, Jaxpr):
            _walk_jaxpr(v, visit)
        elif isinstance(v, (tuple, list)):
            for x in v:
                maybe_recurse(x)

    for eqn in jaxpr.eqns:
        visit(eqn)
        for v in eqn.params.values():
            maybe_recurse(v)


def check_float64(entries: Sequence[TracedEntry]) -> List[Finding]:
    """SC-JAX-F64 over traced engines."""
    out: List[Finding] = []
    for entry in entries:
        hits: List[str] = []

        def visit(eqn, hits=hits):
            for v in eqn.outvars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and str(dt) == "float64":
                    hits.append(str(eqn.primitive))

        _walk_jaxpr(entry.jaxpr.jaxpr, visit)
        if hits:
            uniq = sorted(set(hits))
            out.append(Finding(
                "SC-JAX-F64",
                f"{entry.name}: float64 values inside a float32 engine "
                f"(primitives: {', '.join(uniq)}) — weak-type/literal "
                "promotion leak",
                path=entry.path, line=entry.line))
    return out


def check_callbacks(entries: Sequence[TracedEntry]) -> List[Finding]:
    """SC-JAX-CALLBACK over traced engines."""
    out: List[Finding] = []
    for entry in entries:
        hits: List[str] = []

        def visit(eqn, hits=hits):
            if str(eqn.primitive) in CALLBACK_PRIMITIVES:
                hits.append(str(eqn.primitive))

        _walk_jaxpr(entry.jaxpr.jaxpr, visit)
        if hits:
            out.append(Finding(
                "SC-JAX-CALLBACK",
                f"{entry.name}: host callback in hot path "
                f"({', '.join(sorted(set(hits)))})",
                path=entry.path, line=entry.line))
    return out


def count_sweep_lowerings(
    designs: Optional[Sequence[Tuple[int, int, int]]] = None,
    loads: Sequence[float] = (0.1, 0.3),
    seeds: Sequence[int] = (0, 1),
    max_cycles: int = 12,
) -> Tuple[int, int, List[Finding]]:
    """SC-JAX-RECOMPILE: run a representative (k, num_racks, groups) x
    workload x load x seed grid through `netsim/sweep.py` and require at
    most one fresh `_run_batch` lowering per design point (a warm cache
    from earlier calls in-process may make it fewer).

    Returns (new_lowerings, num_design_points, findings)."""
    from repro.netsim import fluid_jax
    from repro.netsim.sweep import DesignPoint, SweepSpec, run_sweep

    designs = designs or ((4, 6, 1), (4, 10, 1))
    spec = SweepSpec(
        designs=tuple(DesignPoint(k=k, num_racks=n, groups=g)
                      for k, n, g in designs),
        workloads=("shuffle", "permutation"),
        loads=tuple(loads),
        seeds=tuple(seeds),
        max_cycles=max_cycles,
    )
    before = fluid_jax._run_batch._cache_size()
    run_sweep(spec)
    new = fluid_jax._run_batch._cache_size() - before
    path, line = _src_location(fluid_jax._run_batch)
    findings: List[Finding] = []
    if new > len(designs):
        findings.append(Finding(
            "SC-JAX-RECOMPILE",
            f"sweep grid of {len(designs)} design points x "
            f"{spec.scenarios_per_design} scenarios compiled {new} "
            "lowerings — the engine must compile once per design-point "
            "shape, not per load/seed",
            path=path, line=line))
    return new, len(designs), findings


def count_fault_lowerings(
    num_draws: int = 2, max_cycles: int = 6,
) -> Tuple[int, List[Finding]]:
    """SC-JAX-RECOMPILE for the fault path: failure timelines are int32
    *data* operands of `fluid_jax._run_batch_faulted` (the per-step 0/1
    masks are rebuilt inside the scan from the global step counter), so
    running several distinct failure draws through one design point must
    add at most one fresh lowering — zero once warm.

    Returns (new_lowerings, findings)."""
    import numpy as np

    from repro.core.topology import build_opera_topology
    from repro.netsim import fluid_jax
    from repro.netsim.faults import FailureSchedule
    from repro.netsim.sweep import DesignPoint

    topo = build_opera_topology(8, 2, seed=0)
    cfg = DesignPoint(k=4, num_racks=8).to_config()
    demand = np.full((8, 8), 1e6)
    np.fill_diagonal(demand, 0.0)
    before = fluid_jax._run_batch_faulted._cache_size()
    for seed in range(num_draws):
        sched = FailureSchedule.draw(
            topo, seed=seed, link_frac=0.1, switch_count=1, onset_step=2)
        fluid_jax.simulate_rotor_bulk_batch(
            cfg, demand[None], topo=topo, max_cycles=max_cycles,
            faults=[sched])
    new = fluid_jax._run_batch_faulted._cache_size() - before
    path, line = _src_location(fluid_jax._run_batch_faulted)
    findings: List[Finding] = []
    if new > 1:
        findings.append(Finding(
            "SC-JAX-RECOMPILE",
            f"{num_draws} failure draws through one design point compiled "
            f"{new} `_run_batch_faulted` lowerings — fault masks are data; "
            "the engine must lower once per design point, never per draw",
            path=path, line=line))
    return new, findings


def count_sparse_lowerings(
    num_cycles: int = 3, num_demands: int = 2,
) -> Tuple[int, List[Finding]]:
    """SC-JAX-RECOMPILE for the sparse engine: `fluid_jax._run_batch_sparse`
    runs every slice of every cycle in one program, so a whole run —
    and every run at the same (design point, vlb, max_cycles), whatever
    the demand draw — must reuse ONE lowering (the index tensor and the
    demand are data operands, never trace constants).

    Returns (new_lowerings, findings)."""
    import numpy as np

    from repro.core.topology import build_opera_topology
    from repro.netsim import fluid_jax
    from repro.netsim.sweep import DesignPoint

    topo = build_opera_topology(8, 2, seed=0)
    cfg = DesignPoint(k=4, num_racks=8).to_config()
    before = fluid_jax._run_batch_sparse._cache_size()
    rng = np.random.default_rng(0)
    for _ in range(num_demands):
        demand = rng.uniform(0, 1e6, (8, 8))
        np.fill_diagonal(demand, 0.0)
        fluid_jax.simulate_rotor_bulk_batch(
            cfg, demand[None], topo=topo, max_cycles=num_cycles,
            engine="sparse")
    new = fluid_jax._run_batch_sparse._cache_size() - before
    path, line = _src_location(fluid_jax._run_batch_sparse)
    findings: List[Finding] = []
    if new > 1:
        findings.append(Finding(
            "SC-JAX-RECOMPILE",
            f"{num_demands} sparse-engine runs x {num_cycles} cycles x "
            f"{topo.num_slices} slices at one design point compiled {new} "
            "`_run_batch_sparse` lowerings — the index tensor and demands "
            "are data; the slice-loop program must lower once per "
            "(design point, vlb, max_cycles), never per slice or per run",
            path=path, line=line))
    return new, findings


def count_tiled_lowerings(
    loads: Sequence[float] = (0.05, 0.2),
    seeds: Sequence[int] = (0, 1),
) -> Tuple[int, List[Finding]]:
    """SC-JAX-RECOMPILE for the tiled flow engine: the streamed chunk
    program's shapes depend only on the (batch, window_tiles, tile,
    chunk_steps) geometry — the scenario's total flow count, load and
    seed are *data*.  Running a small load x seed grid through
    `simulate_grid(engine="tiled")` twice must add at most one fresh
    `_run_tiled_chunk` lowering, and the second (warm) run must add
    zero.

    The window is kept wide enough that capacity growth never triggers
    a second geometry in this probe (growth lowerings are legitimate
    but would muddy the once-per-design-point count).

    Returns (new_lowerings, findings)."""
    from repro.netsim import flows_jax

    kw = dict(
        num_hosts=16, horizon_s=0.06, dt_s=5e-4, tail_s=0.04,
        tile_size=64, window_tiles=8, chunk_steps=32,
    )
    before = flows_jax._run_tiled_chunk._cache_size()
    flows_jax.simulate_grid(("opera",), ("websearch",), tuple(loads),
                            seeds=tuple(seeds), engine="tiled", **kw)
    cold = flows_jax._run_tiled_chunk._cache_size() - before
    flows_jax.simulate_grid(("opera",), ("websearch",), tuple(loads),
                            seeds=tuple(seeds), engine="tiled", **kw)
    warm = flows_jax._run_tiled_chunk._cache_size() - before - cold
    new = cold + warm
    path, line = _src_location(flows_jax._run_tiled_chunk)
    findings: List[Finding] = []
    if cold > 1 or warm > 0:
        findings.append(Finding(
            "SC-JAX-RECOMPILE",
            f"{len(loads)}x{len(seeds)} tiled flow grid compiled {cold} "
            f"cold + {warm} warm `_run_tiled_chunk` lowerings — chunk "
            "shapes are (batch, window, tile) geometry only; loads and "
            "seeds are data and must never trigger fresh lowerings",
            path=path, line=line))
    return new, findings
