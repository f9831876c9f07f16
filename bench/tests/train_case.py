#!/usr/bin/env python3
"""One run of the training cell at a tiny size on 4 virtual CPU devices
(the harness's look for a TPU skipped), optionally with a fault planted
under the timed path; prints the result line.  `test_train.py` starts it, since the device count must be set
before JAX starts.

    python3 bench/tests/train_case.py <fault> <scratch dir>

Faults: none, state_unchanged, half_batch, no_exchange, altered_answer;
`control` prints the readings of the program and of the control (the
reference in fp8) instead of a run.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# the training cell as it stands beside the benchmark's cells: it returns
# to BENCHMARK.json once the program takes the configuration's epsilon
CONFIG, TRAFFIC = "smollm-360m", "train-dp-8x1024"
RATE = dict(name="train_tokens_per_s", unit="tokens/s", better="higher",
            bound=0.01, source="host_clock", workloads=["tiny-train"])
TINY_ARCH = "smollm-360m-tiny"
TINY = dict(program_config=TINY_ARCH, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=128, vocab_size=256)
# the tiny model's own limits, set as the cell's were, from readings at
# this size: sound runs read up to 1.2e-3 (loss) and 1.6e-3 (the rest);
# the control reads 2.9e-3 (loss), 1.4e-2 (gnorm, grad) and 7e-3 (update)
LIMITS = dict(loss_gap=4e-3, gnorm_gap=4e-3, grad_gap=8e-3, update_gap=4e-3,
              replica_gap=0)


def tiny_root(root: Path) -> None:
    """The real yardstick with a tiny model and batch beside the cell."""
    from bench.drivers.train import program_norm_eps
    from repro.configs import get_config

    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / f"bench/configs/{CONFIG}.json").read_text())
    # at the epsilon the program runs, which the driver insists on, so
    # that the rehearsal tests the harness and the faults
    eps = program_norm_eps(get_config(cfg["program_config"]))
    (root / "bench/configs/tiny-lm.json").write_text(json.dumps(
        {**cfg, **TINY, "rms_norm_eps": eps}))
    tr = json.loads((ROOT / f"bench/traffic/{TRAFFIC}.json").read_text())
    tr.update(per_chip_batch=2, seq_len=16, block_rows=1, batches=4)
    (root / "bench/traffic/tiny-train.json").write_text(json.dumps(tr))
    (root / "bench/limits/tiny-train.json").write_text(json.dumps(LIMITS))
    spec["configs"].append(dict(name="tiny-lm", source="test", reduced=[],
                                file="bench/configs/tiny-lm.json", why="t"))
    spec["workloads"].append(dict(name="tiny-train", config="tiny-lm",
                                  traffic="tiny-train", chips=4, why="t"))
    spec["end_to_end"].append(RATE)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def plant(fault: str) -> None:
    from repro.core import collectives
    from repro.train import opera_dp

    if fault == "state_unchanged":
        update = opera_dp.adamw_update
        opera_dp.adamw_update = lambda c, p, g, s: (p, s,
                                                    update(c, p, g, s)[2])
    elif fault == "half_batch":
        loss = opera_dp.loss_fn
        opera_dp.loss_fn = lambda p, batch, cfg, ctx: loss(
            p, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, cfg, ctx)
    elif fault == "no_exchange":
        collectives.hierarchical_rotor_all_reduce = (
            lambda g, axis, pod_axis=None: g * 4)
    elif fault == "altered_answer":
        make = opera_dp.make_opera_dp_train_step

        def altered(*a, **k):
            step = make(*a, **k)

            def run(state, batch):
                state, metrics = step(state, batch)
                return state, dict(metrics, loss=metrics["loss"] * 1.01)
            return run

        opera_dp.make_opera_dp_train_step = altered
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def register_tiny_arch() -> None:
    """The program's smollm-360m at the tiny widths of `TINY`."""
    from repro.configs.base import get_config, reduced_config, register

    register(TINY_ARCH)(lambda: reduced_config(get_config("smollm-360m")))


def main() -> int:
    fault, scratch = sys.argv[1], Path(sys.argv[2])
    tiny_root(scratch)
    register_tiny_arch()
    import jax

    from bench import run as R
    from repro.launch import compile_cache

    compile_cache.enable_compile_cache = lambda: "off"
    R.find_devices = lambda chips: jax.devices()[:chips]
    if fault == "control":
        res = R.resolve("tiny-train", scratch)
        driver = R.import_file(res["driver"], "tiny_train_driver")
        cell = driver.Cell(res["config"], res["traffic"], 2**31 + 5,
                           jax.devices())
        cell.warm()
        print(json.dumps(dict(cell.calibrate(True), limits=LIMITS)))
        return 0
    plant(fault)
    out = R.run(R.resolve("tiny-train", scratch), 2**31 + 3, 0.5,
                trace=False)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
