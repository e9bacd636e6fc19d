"""Two-stage progressive ANN search (case study 2), runnable.

Builds an MRL-like corpus (full 4KB / reduced 512B vectors), runs the
two-stage search through the fused Pallas distance+top-k kernel, measures
recall vs exact brute force, and prints the modeled platform KQPS.

  PYTHONPATH=src python examples/ann_search.py [--n 20000]
"""
import argparse
import sys
import time
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.ann.corpus import make_corpus, make_queries
from repro.ann.model import AnnWorkload, cpu_sn, gpu_nr, gpu_sn, \
    throughput_kqps
from repro.ann.progressive import exact_topk, recall_at_k, search
from repro.kernels import interpret_mode


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--promote", type=int, default=64)
    args = ap.parse_args()

    print(f"[corpus] {args.n} vectors: full 1024-d (4KB), "
          f"reduced 128-d (512B) — MRL-style nested embeddings")
    full, red, _ = make_corpus(args.n, 1024, 128)
    qs = make_queries(full, args.queries)

    t0 = time.time()
    truth = exact_topk(qs, full, 10)
    t_exact = time.time() - t0

    t0 = time.time()
    pred, stats = search(qs, red, full, k=10, promote=args.promote)
    t_two = time.time() - t0
    rec = recall_at_k(pred, truth)

    print(f"[search] recall@10 = {rec:.4f} (paper claims >98%)")
    print(f"[search] stage-2 re-ranks {args.promote} of {args.n} "
          f"candidates ({100*args.promote/args.n:.2f}%) — "
          f"{stats.stage2_reads} full-vector reads vs "
          f"{stats.stage1_reads} reduced reads")
    print(f"[search] wall: exact {t_exact:.2f}s vs two-stage {t_two:.2f}s "
          f"({'interpreted' if interpret_mode() else 'compiled'} kernel "
          f"on {jax.devices()[0].device_kind})")

    print("\n[model] 8B-vector corpus, 4 SSDs (paper Fig. 10 geometry):")
    for plat in (gpu_sn(), cpu_sn(), gpu_nr()):
        row = [f"{throughput_kqps(plat, AnnWorkload(), d)['kqps']:6.1f}"
               for d in (64e9, 256e9, 512e9)]
        print(f"  {plat.name:7s} KQPS @ 64/256/512GB DRAM: "
              + " / ".join(row))


if __name__ == "__main__":
    main()
