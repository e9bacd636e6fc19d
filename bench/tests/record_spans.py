"""Records the small chip trace that test_bench_spans.py reads
(bench/tests/data/spans.xplane.pb): five ticks inside `bench.window`,
each a `bench.tick` holding the program's own spans as the served path
nests them. In each, `repro.engine.launch` starts a jitted program
(0.44 ms on a TPU v5e), `repro.engine.fetch` waits for it and copies its
2 MB result to the host, and host sleeps follow with the device idle:
4 ms in `repro.engine.sample`, 2 ms in `repro.engine.retire`, 3 ms in
the tick after the step, then 5 ms in `bench.pace`.

  python3 bench/tests/record_spans.py <output directory>     (on a TPU)
"""
import glob
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.obs.trace import enable_spans, span  # noqa: E402


def decode_step(w, x):
    def body(_, h):
        return jnp.tanh(h @ w)
    return jax.lax.fori_loop(0, 8, body, x)


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_spans.py: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(decode_step)
    w = jnp.ones((4096, 4096), jnp.bfloat16) * 1e-3
    x = jnp.ones((256, 4096), jnp.bfloat16)
    np.asarray(f(w, x))
    d = tempfile.mkdtemp()
    A = jax.profiler.TraceAnnotation
    enable_spans(True)
    jax.profiler.start_trace(d)
    with A("bench.window"):
        for k in range(5):
            with A("bench.tick"), span("scheduler.tick"):
                with A("bench.step"), span("engine.step", step=k):
                    with span("engine.launch"):
                        y = f(w, x)
                    with span("engine.fetch"):
                        np.asarray(y)
                    with span("engine.sample"):
                        time.sleep(0.004)
                    with span("engine.retire"):
                        time.sleep(0.002)
                time.sleep(0.003)
            with A("bench.pace"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    enable_spans(False)
    src, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(src, os.path.join(out, "spans.xplane.pb"))
    print(os.path.getsize(src), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
