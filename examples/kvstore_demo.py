"""SSD-resident blocked-Cuckoo KV store (case study 1), runnable.

Fills a table to the paper's 0.7 load factor, serves GETs through the
scalar-prefetch probe kernel, exercises the WAL/coalescing write path,
and prints the modeled Fig. 8 platform throughput.

  PYTHONPATH=src python examples/kvstore_demo.py
"""
import sys
import time
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.kernels import interpret_mode
from repro.kvstore.cuckoo import BlockedCuckooStore
from repro.kvstore.model import (KvWorkload, achievable_throughput,
                                 cpu_sn_platform, gpu_nr_platform,
                                 gpu_sn_platform)


def _kernel_mode() -> str:
    how = "interpreted" if interpret_mode() else "compiled"
    return f"{how} kernel on {jax.devices()[0].device_kind}"


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    nb, slots = 8192, 8
    st = BlockedCuckooStore(n_buckets=nb, slots=slots,
                            dram_cache_items=1024, wal_limit=128)
    rng = np.random.default_rng(0)
    n = int(nb * slots * 0.7)
    keys = rng.choice(np.arange(1, 10**8), size=n, replace=False)
    t0 = time.time()
    for k in keys:
        st.put(int(k), int(k) % 99991)
    st.flush()
    print(f"[store] {n} items inserted at load {st.load_factor():.3f} "
          f"in {time.time()-t0:.1f}s; E[chain]={st.expected_chain_len():.4f}"
          f" observed relocations={st.stats.relocations}")

    # batched GETs through the Pallas probe kernel
    probe = keys[rng.integers(0, n, 4096)].astype(np.int32)
    t0 = time.time()
    found, vals = st.get_batch(probe)
    dt = time.time() - t0
    ok = int((vals[found.astype(bool)]
              == probe[found.astype(bool)] % 99991).sum())
    print(f"[store] batched GET x{len(probe)}: {found.sum()} found, "
          f"{ok} values correct, {dt*1e3:.0f}ms "
          f"({_kernel_mode()}; ~1.5 block reads/GET)")
    print(f"[store] stats: {st.stats}")

    print("\n[model] paper Fig. 8 (5TB store, 80B items, 4 SSDs):")
    wl = KvWorkload(get_frac=0.9, sigma=1.2)
    for plat in (gpu_sn_platform(), cpu_sn_platform(), gpu_nr_platform()):
        r = achievable_throughput(plat, wl, 256e9)
        print(f"  {plat.name:7s}: {r['throughput']/1e6:7.1f} Mops/s "
              f"(limiter: {r['limiter']}, cache hit {r['hit_rate']:.2f})")
    print("  -> GPU+Storage-Next reaches in-memory-class throughput "
          "(FASTER-level) from flash")


if __name__ == "__main__":
    main()
