"""Opera's rotor schedule (NSDI 2020, section 3.3) from a configuration
and its `topo_seed`, built by the benchmark so that the program and the
reference are handed the same schedule and neither reads one the other
made.

The complete graph over N racks is factored into N disjoint symmetric
matchings: N - 1 random perfect matchings, with the diagonal then spread
over N / 2 of them so no slice leaves every rack idle.  Above 128 racks
(`topology: lifted`) a small base factorization is lifted instead.  The
matchings are dealt to the u rotor switches, N / u each in a random
cycling order, redrawn until every slice's live union is connected
(direct construction only).  The switches reconfigure in turn, `groups`
at a time, so the cycle has N / u x u / groups slices.

A matching is a partner vector `p` with p[p[i]] == i; p[i] == i means
rack i has no circuit in it.  The random draws follow the repository's
builder (`core/topology.py`) step for step, so a seed names one schedule.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


def _perfect_matching(avail: np.ndarray, rng) -> Optional[np.ndarray]:
    """A random perfect matching of the graph `avail`: greedy with
    retries, then an exact maximum matching for the sparse tail."""
    n = avail.shape[0]
    for _ in range(30):
        p = np.full(n, -1, dtype=np.int64)
        ok = True
        for v in rng.permutation(n):
            if p[v] >= 0:
                continue
            cands = np.nonzero(avail[v] & (p < 0))[0]
            cands = cands[cands != v]
            if len(cands) == 0:
                ok = False
                break
            u = int(rng.choice(cands))
            p[v], p[u] = u, v
        if ok:
            return p
    import networkx as nx

    g = nx.Graph()
    g.add_nodes_from(range(n))
    ii, jj = np.nonzero(np.triu(avail, 1))
    g.add_edges_from(zip(ii.tolist(), jj.tolist()))
    m = nx.max_weight_matching(g, maxcardinality=True)
    if len(m) * 2 != n:
        return None
    p = np.full(n, -1, dtype=np.int64)
    for a, b in m:
        p[a], p[b] = b, a
    return p


def _spread_diagonal(perfect: List[np.ndarray], rng):
    """N matchings from N - 1 perfect ones: one edge taken from each of
    N / 2 donors, the taken edges forming the N-th matching, so each donor
    keeps two self-loops."""
    n = len(perfect[0])
    idx = list(range(len(perfect)))
    for _ in range(200):
        rng.shuffle(idx)
        covered = np.zeros(n, dtype=bool)
        chosen = []
        for j in idx[: n // 2]:
            p = perfect[j]
            free = np.nonzero(~covered & ~covered[p])[0]
            free = free[free < p[free]]
            if len(free) == 0:
                break
            a = int(rng.choice(free))
            b = int(p[a])
            covered[a] = covered[b] = True
            chosen.append((j, a, b))
        else:
            if not covered.all():
                continue
            out = [m.copy() for m in perfect]
            new = np.arange(n, dtype=np.int64)
            for j, a, b in chosen:
                out[j][a], out[j][b] = a, b
                new[a], new[b] = b, a
            return out + [new]
    return None


def factorization(n: int, seed: int) -> List[np.ndarray]:
    """N random disjoint matchings covering every ordered rack pair once
    (even N)."""
    if n % 2:
        raise ValueError(f"odd rack count {n}")
    for attempt in range(20):
        rng = np.random.default_rng(seed * 1009 + attempt)
        avail = ~np.eye(n, dtype=bool)
        out = []
        for _ in range(n - 1):
            p = _perfect_matching(avail, rng)
            if p is None:
                break
            avail[np.arange(n), p] = False
            avail[p, np.arange(n)] = False
            out.append(p)
        else:
            spread = _spread_diagonal(out, rng)
            if spread is not None:
                return spread
            return out + [np.arange(n, dtype=np.int64)]
    raise RuntimeError(f"could not factor K_{n}")


def lift(base: List[np.ndarray], f: int) -> List[np.ndarray]:
    """Rack (v, c) -> v f + c; base matching p and phase g pair (v, c)
    with (p[v], (g - c) mod f): N f matchings for N f racks."""
    c = np.arange(f)
    out = []
    for p in base:
        for g in range(f):
            lifted = np.empty(len(p) * f, dtype=np.int64)
            for v in range(len(p)):
                lifted[v * f + c] = p[v] * f + ((g - c) % f)
            out.append(lifted)
    return out


def lift_factor(n: int, u: int, max_base: int = 128) -> int:
    """The smallest f with an even base N / f of at least 2u racks and
    at most `max_base`."""
    if n <= max_base:
        return 1
    for f in range(2, n // max(2 * u, 2) + 1):
        b = n // f
        if n % f == 0 and b % 2 == 0 and 2 * u <= b <= max_base:
            return f
    raise ValueError(f"no lift base for {n} racks and {u} switches")


def slice_adjacency(switch_matchings, groups: int) -> np.ndarray:
    """(slices, N, N) float32: 1 where two racks hold a circuit in the
    slice.  Switch s goes dark in slices t = s // groups (mod u / groups)
    and steps to its next matching as it comes back."""
    u = len(switch_matchings)
    per = len(switch_matchings[0])
    n = len(switch_matchings[0][0])
    rounds = u // groups
    t = np.arange(per * rounds)
    adj = np.zeros((len(t), n, n), dtype=np.float32)
    racks = np.arange(n)
    for s, cyc in enumerate(switch_matchings):
        phase = s // groups
        # reconfigurations of switch s at or before slice t
        n_reconf = np.where(t >= phase, (t - phase) // rounds + 1, 0)
        live = t % rounds != phase
        for ti in t[live]:
            p = cyc[n_reconf[ti] % per]
            adj[ti, racks, p] = 1.0
    adj[:, racks, racks] = 0.0
    return adj


def _connected(adj: np.ndarray) -> bool:
    reach = np.zeros(adj.shape[0], dtype=bool)
    reach[0] = True
    while True:
        new = (adj[reach] > 0).any(0) & ~reach
        if not new.any():
            return bool(reach.all())
        reach |= new


def schedule(cfg: dict) -> Tuple[Tuple[np.ndarray, ...], ...]:
    """switch_matchings[s][j]: the j-th matching in switch s's cycle."""
    n, u, g = cfg["num_racks"], cfg["num_circuit_switches"], cfg["groups"]
    seed = cfg["topo_seed"]
    if n % u or u % g:
        raise ValueError("switches must divide racks, groups switches")
    lifted = cfg["topology"] == "lifted"
    if lifted:
        f = lift_factor(n, u)
        base = factorization(n // f, seed)
        fixed = lift(base, f) if f > 1 else base
    elif cfg["topology"] != "direct":
        raise ValueError(f"topology {cfg['topology']!r}")
    out = None
    for attempt in range(24):
        rng = np.random.default_rng(seed + 7919 * attempt)
        matchings = fixed if lifted else factorization(n, seed + 7919 * attempt)
        order = rng.permutation(n)
        per = n // u
        out = []
        for s in range(u):
            cyc = [matchings[j] for j in order[s * per:(s + 1) * per]]
            rng.shuffle(cyc)
            out.append(tuple(cyc))
        out = tuple(out)
        # the lifted fabric keeps its first draw, as the repository's does
        if lifted or all(_connected(a) for a in slice_adjacency(out, g)):
            return out
    return out
