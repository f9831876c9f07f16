"""Rack-to-rack bulk demand matrices (Opera §5.2, §5.6).

A load x offers, from every host, x times its NIC rate over one topology
cycle, placed by the workload's spatial pattern:

* shuffle      every host sends to every host of every other rack;
* permutation  each rack sends all of its hosts' bytes to one other rack
               (a derangement of the racks);
* skew         a fraction of the racks is active, all-to-all among them
               (skew[f, 1] of Opera's reference [29]).
"""
from __future__ import annotations

import numpy as np

from bench.gen.timing import cycle_s

WORKLOADS = ("shuffle", "permutation", "skew")


def _shuffle(n: int, d: int, per_host: float) -> np.ndarray:
    out = np.full((n, n), d * d * per_host / max((n - 1) * d, 1))
    np.fill_diagonal(out, 0.0)
    return out


def _permutation(n: int, d: int, per_host: float,
                 rng: np.random.Generator) -> np.ndarray:
    perm = rng.permutation(n)
    fixed = np.flatnonzero(perm == np.arange(n))
    if fixed.size > 1:          # cycle the fixed points among themselves
        perm[fixed] = np.roll(perm[fixed], 1)
    elif fixed.size == 1:       # swap a lone fixed point with its neighbour
        i = int(fixed[0])
        j = (i + 1) % n
        perm[i], perm[j] = perm[j], perm[i]
    out = np.zeros((n, n))
    out[np.arange(n), perm] = d * per_host
    return out


def _skew(n: int, d: int, per_host: float, frac: float,
          rng: np.random.Generator) -> np.ndarray:
    k = max(2, int(round(frac * n)))
    act = rng.choice(n, k, replace=False)
    out = np.zeros((n, n))
    out[np.ix_(act, act)] = d * per_host / (k - 1)
    out[act, act] = 0.0
    return out


def demand(workload: str, cfg: dict, load: float, rng: np.random.Generator,
           skew_frac: float) -> np.ndarray:
    """(N, N) rack-to-rack bytes for one scenario."""
    per_host = load * cfg["link_rate_gbps"] * 1e9 / 8 * cycle_s(cfg)
    n, d = cfg["num_racks"], cfg["hosts_per_rack"]
    if workload == "shuffle":
        return _shuffle(n, d, per_host)
    if workload == "permutation":
        return _permutation(n, d, per_host, rng)
    if workload == "skew":
        return _skew(n, d, per_host, skew_frac, rng)
    raise ValueError(f"unknown workload {workload!r} (one of {WORKLOADS})")


def demand_batch(cfg: dict, traffic: dict, rng: np.random.Generator
                 ) -> np.ndarray:
    """(B, N, N): workloads x loads x `seeds_per_call` scenarios, each
    drawn with its own generator split from `rng`."""
    rows = []
    for w in traffic["workloads"]:
        for load in traffic["loads"]:
            for _ in range(traffic["seeds_per_call"]):
                sub = np.random.default_rng(rng.integers(2**63))
                rows.append(demand(w, cfg, load, sub, traffic["skew_frac"]))
    return np.stack(rows)
