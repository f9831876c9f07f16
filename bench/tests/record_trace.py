#!/usr/bin/env python3
"""Record the small chip trace that `test_trace.py` reduces.

    python3 bench/tests/record_trace.py [OUT]   # on a TPU; default data/small.xplane.pb

Inside a `bench.window` span: a 512 x 512 float32 matmul program, a
20 ms host sleep, then the same program twice back to back.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host_sleep"):
            time.sleep(0.02)
        f(f(x)).block_until_ready()
    jax.profiler.stop_trace()
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    shutil.rmtree(d)
    print(f"wrote {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
