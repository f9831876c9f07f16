#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for one cell.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--control-seeds 3]

For each seed it builds the cell's inputs as a run does, calls the
program once through the cell's driver (the timed path, at the timed
sizes), and compares that output with the plain reference: the lower
readings.  On the first `--control-seeds` seeds it also puts the
control in the program's place (the reference one precision step below
the configuration's) and compares that with the reference: the upper
readings.  One JSON line per seed, and the largest of each number last.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import run as R
    from repro.launch.compile_cache import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    enable_compile_cache()
    res = R.resolve(args.workload)
    driver = R.import_file(res["driver"], "bench_driver")
    worst = {"program": {}, "control": {}}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = driver.Cell(res["config"], res["traffic"], seed,
                           jax.devices()[:res["cell"]["chips"]])
        if hasattr(cell, "calibrate"):       # readings taken in set-up
            cell.warm()
            line = dict(seed=seed, **cell.calibrate(n < args.control_seeds))
        else:
            got = cell.call(0)
            want = cell.reference(0)
            line = dict(seed=seed, program=cell.compare(0, got, want),
                        guarantees=cell.guarantees())
            if n < args.control_seeds:
                line["control"] = cell.compare(0, cell.control(0), want)
        print(json.dumps(line), flush=True)
        for side, gaps in line.items():
            if isinstance(gaps, dict):
                for k, v in gaps.items():
                    worst.setdefault(side, {})
                    worst[side][k] = max(worst[side].get(k, 0.0), v)
    print(json.dumps(dict(workload=args.workload, device=jax.devices()[0]
                          .device_kind, worst=worst)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
