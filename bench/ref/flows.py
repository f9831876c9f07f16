"""Plain reference of the fixed-step processor-sharing flow model
(Opera §5.3 pools), in jax.numpy.

Every step, the active flows of a class (arrived, with bytes left) share
their pool equally, each capped at its NIC: share = min(pool / k, 1) in
units of one NIC-step.  A flow finishes at the end of the step that
empties it.  At the half-horizon and horizon steps each flow's service
deficit against a dedicated NIC is recorded (the admission test).

`dtype` is float32 for the reference; the control computes the same in
bfloat16, the precision one step below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# completion-time histogram: 96 log-spaced bins over [0.01 ms, 100 s],
# three size classes (< 100 KB, < bulk cutoff, the rest)
HIST_LO_MS, HIST_HI_MS, HIST_BINS = 1e-2, 1e5, 96


@functools.partial(jax.jit, static_argnames=("num_steps",))
def _run(rem0, start, is_bulk, lat_u, bulk_u, allow_mid, allow_end,
         mid_step, end_step, num_steps: int):
    def one(rem0, start, is_bulk, lat_u, bulk_u, allow_mid, allow_end,
            mid_step, end_step):
        dt = rem0.dtype

        def step(carry, s):
            rem, done, dmid, dend = carry
            dmid = jnp.where(s == mid_step,
                             jnp.maximum(rem - allow_mid, 0), dmid)
            dend = jnp.where(s == end_step,
                             jnp.maximum(rem - allow_end, 0), dend)
            active = (s >= start) & (rem > 0)
            for pool, mask in ((lat_u, active & ~is_bulk),
                               (bulk_u, active & is_bulk)):
                k = mask.sum().astype(jnp.float32)
                share = jnp.where(
                    pool > 0,
                    jnp.minimum(pool / jnp.maximum(k, 1.0), 1.0), 0.0
                ).astype(dt)
                rem = jnp.where(mask, rem - jnp.minimum(rem, share), rem)
                done = jnp.where(mask & (rem <= 0) & (done < 0), s + 1, done)
            return (rem, done, dmid, dend), None

        init = (rem0, jnp.full(rem0.shape, -1, jnp.int32),
                jnp.zeros_like(rem0), jnp.zeros_like(rem0))
        out, _ = jax.lax.scan(step, init,
                              jnp.arange(num_steps, dtype=jnp.int32))
        return out

    return jax.vmap(one)(rem0, start, is_bulk, lat_u, bulk_u, allow_mid,
                         allow_end, mid_step, end_step)


def _snapshot_steps(scn: dict):
    return (int(np.ceil(scn["horizon_s"] / 2 / scn["dt_s"])),
            int(np.ceil(scn["horizon_s"] / scn["dt_s"])))


def _allowance(scn: dict, step: int, nic: float) -> np.ndarray:
    """Bytes a dedicated NIC would still owe each flow at `step`."""
    elapsed = np.maximum(step - scn["start_step"], 0) * scn["dt_s"]
    return scn["sizes"] - np.minimum(scn["sizes"], nic * elapsed)


def simulate(scns: list, num_steps: int, dtype=jnp.float32) -> list:
    """Per scenario: done_step (n,) int (-1 unfinished) and the summed
    deficits at the two snapshots, in bytes."""
    n_max = max(s["sizes"].size for s in scns)
    B = len(scns)
    f = {k: np.zeros((B, n_max)) for k in ("rem0", "amid", "aend")}
    start = np.full((B, n_max), num_steps + 1, np.int32)
    is_bulk = np.zeros((B, n_max), bool)
    lat, bulk, mid, end, units = (np.zeros(B) for _ in range(5))
    for b, s in enumerate(scns):
        n = s["sizes"].size
        nic = s["link_gbps"] * 1e9 / 8
        unit = nic * s["dt_s"]
        units[b] = unit
        m, e = _snapshot_steps(s)
        mid[b], end[b] = m, e
        f["rem0"][b, :n] = s["sizes"] / unit
        f["amid"][b, :n] = _allowance(s, m, nic) / unit
        f["aend"][b, :n] = _allowance(s, e, nic) / unit
        start[b, :n] = s["start_step"]
        is_bulk[b, :n] = s["is_bulk"]
        lat[b] = s["lat_pool_Bps"] / nic
        bulk[b] = s["bulk_pool_Bps"] / nic
    _, done, dmid, dend = _run(
        jnp.asarray(f["rem0"], dtype), jnp.asarray(start),
        jnp.asarray(is_bulk), jnp.asarray(lat, dtype),
        jnp.asarray(bulk, dtype), jnp.asarray(f["amid"], dtype),
        jnp.asarray(f["aend"], dtype), jnp.asarray(mid, np.int32),
        jnp.asarray(end, np.int32), num_steps=num_steps)
    done, dmid, dend = (np.asarray(x) for x in (done, dmid, dend))
    out = []
    for b, s in enumerate(scns):
        n = s["sizes"].size
        out.append(dict(
            done_step=done[b, :n].astype(np.int64),
            deficit_mid=float(dmid[b, :n].astype(np.float64).sum()) * units[b],
            deficit_end=float(dend[b, :n].astype(np.float64).sum()) * units[b],
        ))
    return out


def histogram(scn: dict, done_step: np.ndarray, bulk_cutoff: float
              ) -> np.ndarray:
    """(3, 96) completion counts by size class and log-spaced FCT bin."""
    ok = done_step >= 0
    fct_ms = (done_step[ok] * scn["dt_s"] - scn["arr"][ok]) * 1e3
    lo, width = np.log2(HIST_LO_MS), (np.log2(HIST_HI_MS)
                                      - np.log2(HIST_LO_MS)) / HIST_BINS
    with np.errstate(divide="ignore"):
        b = np.floor((np.log2(fct_ms) - lo) / width)
    b = np.clip(b, 0, HIST_BINS - 1).astype(np.int64)
    sizes = scn["sizes"][ok]
    cls = np.where(sizes >= bulk_cutoff, 2, np.where(sizes >= 100e3, 1, 0))
    h = np.zeros((3, HIST_BINS), np.int64)
    np.add.at(h, (cls, b), 1)
    return h


def backlog_frac(scn: dict, deficit_mid: float, deficit_end: float) -> float:
    """Growth of the service deficit over the second half of the
    arrivals, as a share of the bytes offered in it."""
    m, e = _snapshot_steps(scn)
    arrived = lambda step: float(  # noqa: E731
        scn["sizes"][scn["arr"] <= step * scn["dt_s"]].sum())
    offered = max(arrived(e) - arrived(m), 1.0)
    return max(deficit_end - deficit_mid, 0.0) / offered
