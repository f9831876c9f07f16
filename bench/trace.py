"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read.

On a TPU each chip is a plane `/device:TPU:<i>` with the lines
`XLA Modules` (one event per program execution), `XLA Ops` (one event
per HLO instruction, the name its HLO text) and `Async XLA Ops` (copies
and collectives in flight between their start and done).  Host threads are
lines of `/host:CPU`; the benchmark's own spans (`bench.window`,
`bench.call`) and the Python tracer's function events are on the
thread that drives the run.  Device and host events share one clock, in
nanoseconds from the trace's start.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # [start, end) in ns

WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv)")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def op_name(hlo_text: str) -> str:
    """`%fusion.12 = f32[..] fusion(..)` -> `fusion`: the instruction's
    name without its `%` and numeric suffix."""
    head = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged copy of `intervals`."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of merged intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class DeviceTrace:
    index: int
    ops: List[Tuple[str, float, float]]       # (name, start, end)
    modules: List[Tuple[str, float, float]]
    async_ops: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)

    def busy(self, lo: float, hi: float) -> List[Interval]:
        return union(clip([(s, e) for _, s, e in self.ops], lo, hi))

    def collective_exposure(self, lo: float, hi: float
                            ) -> Tuple[float, float]:
        """(collective ns, collective ns with no other op running): a
        collective's time is its ops and its transfer in flight."""
        coll = union(clip([(s, e) for n, s, e in self.ops + self.async_ops
                           if COLLECTIVE.match(n)], lo, hi))
        comp = union(clip([(s, e) for n, s, e in self.ops
                           if not COLLECTIVE.match(n)], lo, hi))
        return length(coll), length(subtract(coll, comp))


@dataclasses.dataclass
class Reduced:
    """Device and host events of one traced window [lo, hi) in ns."""

    devices: List[DeviceTrace]
    host: List[Tuple[str, float, float]]      # driving thread's events
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Union of op intervals, averaged over the chips."""
        return sum(length(d.busy(self.lo, self.hi))
                   for d in self.devices) / len(self.devices) * 1e-9

    def launches(self) -> float:
        """Program executions inside the window, averaged over chips."""
        return sum(sum(1 for _, s, _e in d.modules if self.lo <= s < self.hi)
                   for d in self.devices) / len(self.devices)

    def op_seconds(self) -> Dict[str, float]:
        """Summed op time by name over the window, averaged over chips.
        Nested ops (a loop and its body) are each counted."""
        t: Dict[str, float] = collections.Counter()
        for d in self.devices:
            for n, s, e in d.ops:
                s, e = max(s, self.lo), min(e, self.hi)
                if e > s:
                    t[n] += (e - s) * 1e-9 / len(self.devices)
        return dict(t)

    def collective_s(self) -> Tuple[float, float]:
        """(collective, exposed collective) seconds, averaged over chips."""
        tot = [d.collective_exposure(self.lo, self.hi) for d in self.devices]
        n = len(tot)
        return (sum(a for a, _ in tot) / n * 1e-9,
                sum(b for _, b in tot) / n * 1e-9)

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """Idle time of chip 0 inside the window, summed by the innermost
        host event that covers each gap's midpoint.  Events of one thread
        nest, so one sweep with a stack finds it."""
        busy = self.devices[0].busy(self.lo, self.hi)
        gaps = subtract([(self.lo, self.hi)], busy)
        events = sorted((s, -e, n) for n, s, e in self.host
                        if n != WINDOW_SPAN)
        by: Dict[str, float] = collections.Counter()
        stack: List[Tuple[float, str]] = []
        i = 0
        for s, e in gaps:                     # gaps come sorted
            mid = (s + e) / 2
            while i < len(events) and events[i][0] <= mid:
                stack.append((-events[i][1], events[i][2]))
                i += 1
            while stack and stack[-1][0] <= mid:
                stack.pop()
            # an outer event that ended early can hide under an inner one
            inner = next((n for end, n in reversed(stack) if end > mid),
                         "(no host event)")
            by[inner] += (e - s) * 1e-9
        return sorted(by.items(), key=lambda kv: -kv[1])[:top]

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]


def reduce_trace(path: str, chips: Optional[int] = None) -> Reduced:
    """Read one `.xplane.pb`; the window is the `bench.window` span when
    the trace has one, else the whole extent of the device events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines}
            devices.append(DeviceTrace(
                int(m.group(1)),
                [(op_name(n), s, e) for n, s, e in lines.get("XLA Ops", [])],
                lines.get("XLA Modules", []),
                [(op_name(n), s, e)
                 for n, s, e in lines.get("Async XLA Ops", [])]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(n == WINDOW_SPAN for n, _, _ in evs) or (
                        not host and line.name.startswith("python")):
                    host = evs
    devices.sort(key=lambda d: d.index)
    if chips is not None:
        devices = devices[:chips]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
        for d in devices:
            first = min((s for _, s, _ in d.ops + d.modules), default=lo)
            skew = max(0.0, lo - first)
            d.ops, d.modules, d.async_ops = (
                [(n, s + skew, e + skew) for n, s, e in evs]
                for evs in (d.ops, d.modules, d.async_ops))
    else:
        ends = [(s, e) for d in devices for _, s, e in d.ops]
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    return Reduced(devices, host, lo, hi)
