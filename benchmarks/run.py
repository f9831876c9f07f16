"""Benchmark harness: one module per paper table/figure + framework benches.

    PYTHONPATH=src python -m benchmarks.run [--only fig08,fig12] [--skip ...]

Exits 1 when any module raises or any of its checks fails, and 2 on an
unknown module name.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.common import save

MODULES = [
    ("sec41_cycle_time", "benchmarks.sec41_cycle_time"),
    ("fig04_path_lengths", "benchmarks.fig04_path_lengths"),
    ("fig08_shuffle", "benchmarks.fig08_shuffle"),
    ("fig07_datamining", "benchmarks.fig07_datamining"),
    ("fig09_websearch", "benchmarks.fig09_websearch"),
    ("fig10_mixed", "benchmarks.fig10_mixed"),
    ("fig11_faults", "benchmarks.fig11_faults"),
    ("fig12_cost", "benchmarks.fig12_cost"),
    ("netsim_sweep", "benchmarks.netsim_sweep"),
    ("perf_track", "benchmarks.perf_track"),
    ("table1_appD", "benchmarks.table1_appD"),
    ("bench_rotor_collectives", "benchmarks.bench_rotor_collectives"),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--skip", default="")
    args = ap.parse_args(argv)
    only = set(filter(None, args.only.split(",")))
    skip = set(filter(None, args.skip.split(",")))
    unknown = (only | skip) - {name for name, _ in MODULES}
    if unknown:
        print(f"unknown benchmark module(s): {sorted(unknown)}")
        sys.exit(2)

    results, failed = {}, []
    t0 = time.time()
    for name, modpath in MODULES:
        if only and name not in only:
            continue
        if name in skip:
            continue
        try:
            mod = __import__(modpath, fromlist=["run"])
            out = mod.run()
            save(name, out)
            checks = out.get("checks", {})
            results[name] = dict(
                ok=all(checks.values()) if checks else True, checks=checks
            )
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failed.append((name, repr(e)))
            results[name] = dict(ok=False, error=repr(e))

    print("\n" + "=" * 78)
    print("== BENCHMARK SUMMARY")
    print("=" * 78)
    for name, r in results.items():
        status = "OK  " if r.get("ok") else "FAIL"
        nchk = len(r.get("checks", {}))
        npass = sum(bool(v) for v in r.get("checks", {}).values())
        print(f"  [{status}] {name:26s} {npass}/{nchk} checks")
    print(f"  total: {time.time()-t0:.1f}s")
    save("summary", results)
    if failed:
        print(f"\n{len(failed)} benchmark(s) errored: {failed}")
    bad_checks = [name for name, r in results.items()
                  if "checks" in r and not r["ok"]]
    if bad_checks:
        print(f"\n{len(bad_checks)} benchmark(s) failed checks: {bad_checks}")
    if failed or bad_checks:
        sys.exit(1)


if __name__ == "__main__":
    main()
