"""Records the small chip trace that test_bench_xplane.py reads
(bench/tests/data/small.xplane.pb): five runs of a jitted program named
`decode_step`, each inside `bench.tick` / `bench.step` annotations with
10 ms of host sleep after it inside the tick and 5 ms in `bench.pace`,
all inside `bench.window`.

  python3 bench/tests/record_trace.py <output directory>     (on a TPU)
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def decode_step(w, x):
    return jnp.tanh(x @ w) @ w


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(decode_step)
    w = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    x = jnp.ones((256, 2048), jnp.bfloat16)
    f(w, x).block_until_ready()
    d = tempfile.mkdtemp()
    A = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(d)
    with A("bench.window"):
        for _ in range(5):
            with A("bench.tick"):
                with A("bench.step"):
                    f(w, x).block_until_ready()
                time.sleep(0.010)
            with A("bench.pace"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    print(os.path.getsize(src), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
