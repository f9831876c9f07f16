"""Opera's cycle-time arithmetic (NSDI 2020 §4.1, Appendix B), kept with
the benchmark so the reference and the traffic do not lean on the
program's copy.

epsilon = worst_hops x (queue drain + propagation), slice = epsilon + r,
duty = 1 - r / ((u / groups) x slice), cycle = (N / groups) slices.
"""
from __future__ import annotations

WORST_HOPS = 5


def slice_us(cfg: dict) -> float:
    drain_us = cfg["queue_bytes"] * 8 / (cfg["link_rate_gbps"] * 1e3)
    eps = WORST_HOPS * (drain_us + cfg["prop_delay_us"])
    return eps + cfg["reconfig_delay_us"]


def num_slices(cfg: dict) -> int:
    return cfg["num_racks"] // cfg["groups"]


def cycle_s(cfg: dict) -> float:
    return num_slices(cfg) * slice_us(cfg) * 1e-6


def duty_cycle(cfg: dict) -> float:
    rounds = cfg["num_circuit_switches"] // cfg["groups"]
    return 1.0 - cfg["reconfig_delay_us"] / (rounds * slice_us(cfg))


def slice_capacity_bytes(cfg: dict) -> float:
    """Bytes one live circuit carries in one slice, duty-derated."""
    return (cfg["link_rate_gbps"] * 1e9 / 8 * slice_us(cfg) * 1e-6
            * duty_cycle(cfg))
