"""The program's own host spans (`repro.obs`), read from a reduced trace
(`bench/trace.py`): they lie on the driving thread's line beside the
benchmark's spans, on the device trace's clock."""
from __future__ import annotations

from typing import Iterable, Optional

from bench.trace import Reduced, clip, length


def span_seconds(red: Reduced, names: Iterable[str]) -> Optional[float]:
    """Summed time of the host events named in `names`, clipped to the
    window [lo, hi); None where none of them ran in it (a program
    without these spans, or a path that bypasses them)."""
    names = set(names)
    spans = clip([(s, e) for n, s, e in red.host if n in names],
                 red.lo, red.hi)
    return length(spans) * 1e-9 if spans else None
