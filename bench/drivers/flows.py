"""Flow-level ladders through `flows_jax.simulate_flows_batch`.

One call simulates one scenario per load of the ladder, every flow from
arrival to completion, for the horizon plus its tail, on the engine
`auto` picks.  Its work is batch x steps scenario-steps.  `correct`
compares completion-time histograms and the admission test's backlog with `bench/ref/flows.py`.
"""
from __future__ import annotations

import numpy as np

from bench.gen import flow_arrivals
from bench.ref import flows as ref

RATE_METRIC = "flow_steps_per_s"


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        from repro.netsim import flows, flows_jax

        self.cfg, self.traffic = cfg, traffic
        self.steps = flow_arrivals.num_steps(traffic)
        rng = np.random.default_rng(seed)
        self.inputs = [flow_arrivals.scenario_batch(cfg, traffic, rng)
                       for _ in range(traffic["batches"])]
        self.scenarios = [
            [flows.FlowScenario(network="opera", workload=traffic["workload"],
                                seed=0, **s) for s in batch]
            for batch in self.inputs]
        if {s.steps for b in self.scenarios for s in b} != {self.steps}:
            raise ValueError("program and benchmark disagree on step count")
        self.simulate = flows_jax.simulate_flows_batch
        n_max = max(s["sizes"].size for b in self.inputs for s in b)
        self.backend = flows_jax.resolve_flow_engine("auto", n_max)
        self.work = len(traffic["loads"]) * self.steps
        self.describe = (f"{cfg['name']} {traffic['workload']} loads="
                         f"{traffic['loads']} steps={self.steps} flows<="
                         f"{n_max} backend={self.backend}")

    def warm(self):
        """Window geometry follows the data, so every batch is run once."""
        for i in range(len(self.inputs)):
            self.call(i)

    def call(self, i: int):
        r = self.simulate(self.scenarios[i % len(self.inputs)],
                          engine="auto")
        return dict(hists=[np.asarray(h) for h in r.hists],
                    backlog=[x.backlog_frac for x in r.results])

    def release(self):
        self.simulate = None

    def reference(self, i: int, dtype: str = "float32"):
        import jax.numpy as jnp

        scns = self.inputs[i % len(self.inputs)]
        out = ref.simulate(scns, self.steps, getattr(jnp, dtype))
        cutoff = self.cfg["flow_model"]["bulk_cutoff_bytes"]
        return dict(
            hists=[ref.histogram(s, o["done_step"], cutoff)
                   for s, o in zip(scns, out)],
            backlog=[ref.backlog_frac(s, o["deficit_mid"], o["deficit_end"])
                     for s, o in zip(scns, out)],
            flows=[s["sizes"].size for s in scns])

    def control(self, i: int):
        """The reference one precision step below float32: bfloat16."""
        return self.reference(i, "bfloat16")

    def compare(self, i: int, got: dict, want: dict) -> dict:
        """hist_gap: largest share of a scenario's flows whose completion
        lands in another bin (or not at all); backlog_gap: largest
        difference in the admission test's deficit growth."""
        hist = max(np.abs(g - w).sum() / (2 * max(n, 1)) for g, w, n in
                   zip(got["hists"], want["hists"], want["flows"]))
        backlog = max(abs(g - w) for g, w in
                      zip(got["backlog"], want["backlog"]))
        return dict(hist_gap=float(hist), backlog_gap=float(backlog))

    def guarantees(self) -> dict:
        return {}
