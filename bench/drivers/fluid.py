"""Bulk-traffic sweeps through `fluid_jax.simulate_rotor_bulk_batch`.

One call simulates a batch of rack demand matrices (workloads x loads x
seeds) for `max_cycles` topology cycles on the engine `auto` picks.  Its
work is the horizon asked for: batch x max_cycles x slices per cycle
scenario-slices.  The rotor schedule is the benchmark's own
(`bench/gen/opera_schedule.py`), handed to the program as its topology
and to the reference as slice adjacencies.  `correct` compares the
cumulative delivered and wire bytes after every slice with
`bench/ref/fluid.py`.
"""
from __future__ import annotations

import numpy as np

from bench.gen import opera_schedule, rack_demand, timing
from bench.ref import fluid as ref

RATE_METRIC = "fluid_slices_per_s"


def validate_topology(adj: np.ndarray, cfg: dict) -> int:
    """Number of Opera schedule guarantees the slice tensor breaks: 0/1
    circuits, symmetric, no self-circuits, at most u circuits per rack
    per slice, and a direct circuit for every rack pair in every cycle."""
    n, u = cfg["num_racks"], cfg["num_circuit_switches"]
    bad = int(adj.shape != (timing.num_slices(cfg), n, n))
    bad += int(not np.isin(adj, (0.0, 1.0)).all())
    bad += int(not all(np.array_equal(a, a.T) for a in adj))
    bad += int(np.einsum("tii->", adj) != 0)
    bad += int(adj.sum(2).max() > u)
    cover = adj.sum(0)
    np.fill_diagonal(cover, 1)
    bad += int(cover.min() < 1)
    return bad


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        from repro.configs.opera_paper import OperaNetConfig
        from repro.core import topology
        from repro.netsim import fluid_jax

        if not traffic["vlb"]:
            raise ValueError("the reference models RotorLB with VLB on")
        self.cfg, self.traffic = cfg, traffic
        self.net = OperaNetConfig(
            name=cfg["name"], k=cfg["k"], num_racks=cfg["num_racks"],
            hosts_per_rack=cfg["hosts_per_rack"],
            num_circuit_switches=cfg["num_circuit_switches"],
            link_rate_gbps=cfg["link_rate_gbps"],
            prop_delay_us=cfg["prop_delay_us"],
            reconfig_delay_us=cfg["reconfig_delay_us"],
            queue_bytes=cfg["queue_bytes"], mtu=cfg["mtu"],
            groups=cfg["groups"])
        self.switch_matchings = opera_schedule.schedule(cfg)
        self.topo = topology.OperaTopology(
            num_racks=cfg["num_racks"],
            num_switches=cfg["num_circuit_switches"],
            switch_matchings=self.switch_matchings, groups=cfg["groups"])
        self.simulate = fluid_jax.simulate_rotor_bulk_batch
        self.backend = fluid_jax.resolve_engine("auto", cfg["num_racks"])
        rng = np.random.default_rng(seed)
        self.inputs = [rack_demand.demand_batch(cfg, traffic, rng)
                       for _ in range(traffic["batches"])]
        batch = self.inputs[0].shape[0]
        self.work = batch * traffic["max_cycles"] * timing.num_slices(cfg)
        self.describe = (f"{cfg['name']} B={batch} max_cycles="
                         f"{traffic['max_cycles']} backend={self.backend}")

    def warm(self):
        """Every batch has one shape, so one call compiles all."""
        self.call(0)

    def call(self, i: int):
        r = self.simulate(self.net, self.inputs[i % len(self.inputs)],
                          vlb=True, max_cycles=self.traffic["max_cycles"],
                          topo=self.topo, engine="auto")
        return dict(finished=r.finished_frac, wire=r.wire_bytes,
                    slices_run=r.slices_run)

    def release(self):
        self.simulate = None

    def reference(self, i: int, matmul: str = "exact"):
        """What the reference gives for call `i`'s inputs, in the form
        `call` returns, with the whole wire trajectory besides."""
        dem = self.inputs[i % len(self.inputs)]
        total = dem.sum((1, 2))
        done, wire = ref.trajectories(
            self.adjacency(), dem, timing.slice_capacity_bytes(self.cfg),
            self.traffic["max_cycles"], matmul)
        # a scenario is done at the first slice with all but 1e-5 of its
        # bytes delivered, or at the horizon
        hit = done >= (total * 0.99999)[:, None]
        last = np.where(hit.any(1), hit.argmax(1), done.shape[1] - 1)
        rows = np.arange(done.shape[0])
        return dict(finished=done / np.maximum(total, 1.0)[:, None],
                    wire=wire[rows, last], slices_run=last + 1,
                    wire_t=wire / np.maximum(total, 1.0)[:, None],
                    total=total)

    def control(self, i: int):
        """The reference with its relay product one precision step below
        the configuration's HIGHEST: three bfloat16 passes."""
        return self.reference(i, "bf16x3")

    def adjacency(self) -> np.ndarray:
        if not hasattr(self, "_adj"):
            self._adj = opera_schedule.slice_adjacency(
                self.switch_matchings, self.cfg["groups"])
        return self._adj

    def compare(self, i: int, got: dict, want: dict) -> dict:
        """Largest gaps, as shares of each scenario's demand."""
        rows = np.arange(got["finished"].shape[0])
        at = got["slices_run"] - 1
        wire_got = got["wire"] / np.maximum(want["total"], 1.0)
        return dict(
            traj_gap=float(np.abs(got["finished"] - want["finished"]).max()),
            wire_gap=float(np.abs(wire_got - want["wire_t"][rows, at]).max()),
        )

    def guarantees(self) -> dict:
        return dict(topology_faults=validate_topology(self.adjacency(),
                                                      self.cfg))
