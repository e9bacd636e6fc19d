"""Set-up shared by the benchmark's scripts, imported before JAX: the
persistent compilation cache at one fixed path inside the checkout
(JAX reads both variables when it is imported, and the program takes
the directory from the variable), and the checkout root and the
program's sources on the import path."""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE = ROOT / ".bench_cache" / "jax"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
