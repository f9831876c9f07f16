#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in `BENCHMARK.json`; it names a
configuration (`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`), and the traffic names the driver
(`bench/drivers/<driver>.py`) that calls the program.  The run builds its
inputs from the seed, warms up every shape (set-up), calls the program
in a loop for `--seconds` (the window), then compares a sample of the
window's outputs, drawn from the seed, with the plain reference and the
limits in `bench/limits/<cell>.json`.

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` the window, cut to the traffic's `trace_seconds`, runs under
the profiler and the metrics are the per-layer metrics of
`BENCHMARK.json` that apply to the cell, each read by
`bench/metrics/<metric>.py`.  The last line of standard output is one
JSON object; the last lines of standard error give each compared number
beside its limit.  The result line's `window` tells how the window's
time was spread over its calls.  Without a TPU, or with fewer chips than the
cell asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class SpecError(ValueError):
    pass


class NoDevice(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(one of {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{workload}.json")

    def applies(m):
        return workload in m.get("workloads", [workload])

    end_to_end = [m for m in spec["end_to_end"] if applies(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in spec["per_layer"] if applies(m)
                 and m["moves"] in reported]
    return dict(cell=cell, config=config, traffic=traffic, limits=limits,
                end_to_end=end_to_end, per_layer=per_layer,
                driver=root / "bench" / "drivers" / f"{traffic['driver']}.py",
                metric_files={m["name"]: root / "bench" / "metrics"
                              / f"{m['name']}.py" for m in per_layer})


def import_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Reservoir:
    """Keeps `k` of a stream's items, each equally likely, with the
    choice drawn from `rng`: the window's sample for the reference."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.items[j] = item


def window_stats(t_start: float, ends: list) -> dict:
    """How the window's time was spread over its calls: a run slowed by
    a few stalled calls shows a steady median and a large excess."""
    import numpy as np

    d = np.diff([t_start] + ends)
    med = float(np.median(d))
    return dict(call_median_s=med, call_p90_s=float(np.quantile(d, 0.9)),
                call_max_s=float(d.max()),
                slow_call_excess_s=float(np.sum(d[d > 1.5 * med] - med)))


def device_info(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    d = devices[0]
    return dict(platform=d.platform, kind=d.device_kind, count=len(devices),
                memory_peak_bytes=max(peaks))


def find_devices(chips: int) -> list:
    """The first `chips` TPU chips; NoDevice where JAX finds no TPU or
    fewer chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform "
                       f"{devices[0].platform!r}); no result")
    if len(devices) < chips:
        raise NoDevice(f"{len(devices)} devices, the cell needs {chips}; "
                       "no result")
    return devices[:chips]


def run(res: dict, seed: int, seconds: float, trace: bool) -> dict:
    """One run of a resolved cell; returns the result line's object."""
    import jax
    import numpy as np

    chips = res["cell"]["chips"]
    devices = find_devices(chips)

    from repro.launch.compile_cache import enable_compile_cache

    # the checkout's own cache, whatever the environment names: only the
    # first run of a cell in a checkout compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # programs loaded: compiled, or read from the persistent cache
    compiles, cache_hits = [0], [0]

    def on_load(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    def on_hit(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_load)
    jax.monitoring.register_event_listener(on_hit)

    driver = import_file(res["driver"], f"bench_driver_{res['driver'].stem}")
    cell = driver.Cell(res["config"], res["traffic"], seed, devices)
    log(f"{res['cell']['name']}: {cell.describe}")
    log(f"backend: {cell.backend}")
    cell.warm()
    setup_s = time.perf_counter() - T0
    log(f"setup {setup_s:.3f}s, {compiles[0]} programs loaded, "
        f"{cache_hits[0]} of them from the compile cache")

    if trace:
        # a traced window is kept short: the profiler drops a chip's events
        # past a few million, and reading them costs seconds a million
        seconds = min(seconds, res["traffic"]["trace_seconds"])
    rng = np.random.default_rng(seed)
    sample = Reservoir(res["traffic"]["check_calls"], rng)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    compiles_before = compiles[0]
    if trace:
        # no Python tracer: its events would outnumber the device's many
        # times over; the host's own spans still name the idle gaps
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    calls, ends = 0, []
    with jax.profiler.TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                sample.offer((calls, cell.call(calls)))
            calls += 1
            ends.append(time.perf_counter())
            if ends[-1] - t_start >= seconds:
                break
        if hasattr(cell, "wait"):          # calls that return before the work
            cell.wait()
        elapsed = time.perf_counter() - t_start
    stats = window_stats(t_start, ends)
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles[0] - compiles_before
    log(f"window {elapsed:.3f}s, {calls} calls, {window_compiles} programs "
        f"loaded; {json.dumps(stats)}")
    device = device_info(devices)

    cell.release()
    gc.collect()
    checks, failed = {}, 0
    if hasattr(cell, "check"):            # readings taken in set-up
        gap_sets = [cell.check()]
    else:                                 # a sample of the window's outputs
        gap_sets = [cell.compare(i, got, cell.reference(i)) for i, got in
                    sorted(sample.items, key=lambda kv: kv[0])]
    for gaps in gap_sets:
        failed += any(v > res["limits"][k] for k, v in gaps.items())
        for k, v in gaps.items():
            checks[k] = max(checks.get(k, 0.0), v)
    checks.update(cell.guarantees())
    if set(checks) != set(res["limits"]):
        raise SpecError(f"compared {sorted(checks)}, limits for "
                        f"{sorted(res['limits'])}")
    correct = all(np.isfinite(v) and v <= res["limits"][k]
                  for k, v in checks.items())

    out = dict(correct=bool(correct), attempted=calls, failed=failed)
    if trace:
        from bench.trace import reduce_trace

        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        red = reduce_trace(str(path), chips)
        ctx = dict(trace=red, work=calls * cell.work, calls=calls,
                   chips=chips, config=res["config"], traffic=res["traffic"],
                   device_kind=device["kind"])
        metrics = {}
        for m in res["per_layer"]:
            reader = import_file(res["metric_files"][m["name"]],
                                 f"bench_metric_{len(metrics)}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
        device.update(busy_s=red.busy_s(), window_s=red.window_s)
        out["breakdown"] = dict(
            device_ops=[[n, s] for n, s in red.top_ops(10)],
            idle_gaps=[[n, s] for n, s in red.idle_gaps(10)])
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {driver.RATE_METRIC: calls * cell.work / elapsed,
                  "setup_s": setup_s}
        metrics = {}
        for m in res["end_to_end"]:
            if m["name"] not in values:
                raise SpecError(f"{m['name']} is not measured by "
                                f"drivers/{res['driver'].name}")
            metrics[m["name"]] = dict(value=values[m["name"]], unit=m["unit"])
    out.update(metrics=metrics, device=device,
               compiles_in_window=window_compiles, window=stats)
    out["checks"] = {k: dict(value=v, limit=res["limits"][k])
                     for k, v in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under {src}; no result", file=sys.stderr)
        return 2
    if sys.path and Path(sys.path[0]).resolve() == BENCH:
        sys.path[0] = str(ROOT)       # import the yardstick as `bench.*`
    sys.path[:0] = [str(src), str(ROOT)]
    try:
        res = resolve(args.workload)
    except (SpecError, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    try:
        out = run(res, args.seed, args.seconds, bool(args.trace))
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
