"""Poisson flow arrivals with sizes from the published CDFs (Opera §5.1,
Fig. 1), and the flow model's capacity pools (§5.3).

Flows arrive uniformly over the horizon (a Poisson process conditioned on
its count), at a rate that offers `load` of the aggregate host bandwidth;
sizes are drawn by inverse CDF, log-linear between the published points,
with the first point an atom.  On Opera, flows of 15 MB and more take
the bulk pool after a uniform wait of at most one cycle; the rest take
the latency pool after one base RTT.
"""
from __future__ import annotations

import numpy as np

# (size_bytes, P[size <= s]): websearch from DCTCP (Alizadeh et al.),
# datamining from VL2 (Greenberg et al.).
CDFS = {
    "websearch": [
        (6e3, 0.15), (13e3, 0.20), (19e3, 0.30), (33e3, 0.40), (53e3, 0.53),
        (133e3, 0.60), (667e3, 0.70), (1.3e6, 0.80), (3e6, 0.90),
        (6e6, 0.96), (10e6, 0.99), (14e6, 1.00),
    ],
    "datamining": [
        (100, 0.03), (300, 0.2), (1e3, 0.50), (3e3, 0.68), (10e3, 0.80),
        (100e3, 0.90), (1e6, 0.95), (10e6, 0.973), (100e6, 0.99),
        (250e6, 0.995), (1e9, 1.00),
    ],
}


def mean_flow_size(name: str) -> float:
    """E[S] in closed form: a log-linear bin (s0, s1] carries
    (p1 - p0) (s1 - s0) / ln(s1 / s0) bytes, the first point p0 s0."""
    cdf = CDFS[name]
    total = cdf[0][1] * cdf[0][0]
    for (s0, p0), (s1, p1) in zip(cdf, cdf[1:]):
        total += (p1 - p0) * (s1 - s0) / np.log(s1 / s0)
    return float(total)


def sample_sizes(name: str, n: int, rng: np.random.Generator) -> np.ndarray:
    cdf = CDFS[name]
    sizes = np.array([s for s, _ in cdf])
    probs = np.array([p for _, p in cdf])
    u = rng.uniform(0.0, 1.0, n)
    idx = np.clip(np.searchsorted(probs, u), 1, len(cdf) - 1)
    s0, s1 = sizes[idx - 1], sizes[idx]
    p0, p1 = probs[idx - 1], probs[idx]
    frac = np.clip((u - p0) / np.maximum(p1 - p0, 1e-12), 0.0, 1.0)
    return np.exp(np.log(s0) + frac * (np.log(s1) - np.log(s0)))


def pools_Bps(flow_model: dict, num_hosts: int, link_gbps: float):
    """(latency pool, bulk pool) in bytes/s for Opera: the latency class
    gets eta_indirect x duty x u / (d x avg_hops) of host bandwidth, the
    bulk class eta_direct x duty x u / d."""
    m = flow_model
    agg = num_hosts * link_gbps * 1e9 / 8.0
    lat = m["eta_indirect"] * m["duty"] * m["u"] / (m["d"] * m["avg_hops"])
    bulk = m["eta_direct"] * m["duty"] * m["u"] / m["d"]
    return lat * agg, bulk * agg


def scenario(cfg: dict, traffic: dict, load: float,
             rng: np.random.Generator) -> dict:
    """One Opera scenario as plain arrays and scalars."""
    m = cfg["flow_model"]
    num_hosts = cfg["num_racks"] * cfg["hosts_per_rack"]
    link = cfg["link_rate_gbps"]
    horizon, dt = traffic["horizon_s"], traffic["dt_s"]
    lam = load * num_hosts * link * 1e9 / 8.0 / mean_flow_size(
        traffic["workload"])
    n = max(int(lam * horizon), 1)
    arr = np.sort(rng.uniform(0, horizon, n))
    sizes = sample_sizes(traffic["workload"], n, rng)
    is_bulk = sizes >= m["bulk_cutoff_bytes"]
    delay = np.where(is_bulk, rng.uniform(0, m["cycle_ms"] / 1e3, n),
                     m["base_rtt_us"] * 1e-6)
    lat_pool, bulk_pool = pools_Bps(m, num_hosts, link)
    return dict(
        load=float(load), horizon_s=horizon, dt_s=dt,
        tail_s=traffic["tail_s"], num_hosts=num_hosts, link_gbps=link,
        arr=arr, sizes=sizes,
        start_step=np.ceil((arr + delay) / dt).astype(np.int32),
        is_bulk=is_bulk, lat_pool_Bps=float(lat_pool),
        bulk_pool_Bps=float(bulk_pool),
    )


def num_steps(traffic: dict) -> int:
    return (int(traffic["horizon_s"] / traffic["dt_s"])
            + int(traffic["tail_s"] / traffic["dt_s"]))


def scenario_batch(cfg: dict, traffic: dict, rng: np.random.Generator
                   ) -> list:
    """One call's scenarios: one per load of the ladder."""
    return [scenario(cfg, traffic, load,
                     np.random.default_rng(rng.integers(2**63)))
            for load in traffic["loads"]]
