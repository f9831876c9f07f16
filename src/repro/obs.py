"""Host spans and counters of the simulator engines' host code.

A span is a `jax.profiler.TraceAnnotation`: it lands on the calling
thread's host line of the profiler trace, on the device trace's clock,
and records nothing while no profiler session runs.  Counters are
process-wide sums, always on; the engines add to them once per call or
per chunk, never per slice or step.  Every name is declared below with
one line saying what it covers.
"""
from __future__ import annotations

import collections
import threading
from typing import Dict

import jax

SPANS = {
    "fluid.prepare": "demand normalisation, schedule export, masks, uploads",
    "fluid.run": "the dense, sparse or faulted program call",
    "fluid.readback": "np.asarray of the trajectories, waiting for the device",
    "fluid.stats": "the per-row float64 completion statistics",
    "fluid.sparse.loop": "the sparse engine's slice-loop program call",
    "flows.prepare": "dense packing and upload, or tiled states and constants",
    "flows.run": "the dense program call, or the tiled chunk loop",
    "flows.readback": "np.asarray of the results, waiting for the device",
    "flows.finalize": "per-scenario results from the read-back arrays",
    "flows.tiled.fill": "one chunk's window buffers allocated and filled",
    "flows.tiled.upload": "one chunk's window operands put on the device",
    "flows.tiled.chunk": "one chunk's jitted scan dispatched",
    "flows.tiled.readback": "np.asarray of one chunk's remaining bytes",
    "flows.tiled.retire": "one chunk's write-back and drained-tile retirement",
}

COUNTERS = {
    "fluid.h2d_bytes": "bytes the fluid engine uploads, at their device dtype",
    "fluid.scenario_slices": "B x max_cycles x slices per cycle, as requested",
    "flows.h2d_bytes": "bytes the flow engine uploads, over every chunk",
    "flows.scenario_steps": "B x steps, as requested",
}

_counts: Dict[str, int] = collections.Counter()
_lock = threading.Lock()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named `name`, which must be declared in `SPANS`."""
    if name not in SPANS:
        raise KeyError(f"span {name!r} is not declared in obs.SPANS")
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int) -> None:
    """Add `n` to the counter `name`, which must be declared in `COUNTERS`."""
    if name not in COUNTERS:
        raise KeyError(f"counter {name!r} is not declared in obs.COUNTERS")
    with _lock:
        _counts[name] += int(n)


def counters() -> Dict[str, int]:
    """A copy of every counter's sum since the process started or `reset`."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    with _lock:
        _counts.clear()
