"""End-to-end training driver with fault tolerance: trains an LM on the
synthetic pipeline with checkpointing, then simulates a crash and proves
byte-exact resume. `--scale 100m` trains a ~100M-parameter model (slow on
1 CPU core; default `10m` finishes in minutes).

  PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import shutil
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.launch.train import RunConfig, run
from repro.models.config import AttnSpec, FfnSpec, ModelConfig

SCALES = {
    # name: (d_model, layers, d_ff, vocab)  ~params
    "1m": (128, 4, 512, 2048),          # ~1.3M
    "10m": (320, 6, 1280, 8192),        # ~13M
    "100m": (640, 12, 2560, 32000),     # ~105M
}


def lm_config(scale: str) -> ModelConfig:
    d, L, f, v = SCALES[scale]
    return ModelConfig(
        name=f"lm-{scale}", d_model=d, vocab=v, n_groups=L,
        pattern=((AttnSpec(n_heads=d // 64, n_kv=max(d // 128, 1),
                           head_dim=64), FfnSpec(d_ff=f)),),
        max_seq=1024, rope_theta=1e4, tie_embeddings=True)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="10m", choices=list(SCALES))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    import repro.launch.train as T
    cfg = lm_config(args.scale)
    print(f"[example] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps of batch {args.batch} x seq {args.seq}")

    # monkey-patch the registry hook: run() accepts any arch via get_config,
    # so register ours
    import repro.configs as C
    C._MOD[cfg.name] = None
    orig = C.get_config
    C.get_config = lambda name, reduced=False: (
        cfg if name == cfg.name else orig(name, reduced))
    T.get_config = C.get_config

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    rc = RunConfig(arch=cfg.name, reduced=True, steps=args.steps,
                   batch=args.batch, seq=args.seq, lr=1e-3,
                   ckpt_dir=args.ckpt_dir, ckpt_every=args.steps // 4)
    out = run(rc)
    print(f"[example] phase 1 final loss {out['final_loss']:.4f}")

    # simulate a crash at 100%: re-run — must resume, not restart
    print("[example] simulating preemption: relaunching the driver ...")
    rc2 = RunConfig(arch=cfg.name, reduced=True, steps=args.steps + 40,
                    batch=args.batch, seq=args.seq, lr=1e-3,
                    ckpt_dir=args.ckpt_dir, ckpt_every=20)
    out2 = run(rc2)
    print(f"[example] resumed + {len(out2['losses'])} more steps, "
          f"final loss {out2['final_loss']:.4f} "
          f"(started from checkpointed step, not 0)")
    assert len(out2["losses"]) <= 40 + 1, "resume failed: retrained"


if __name__ == "__main__":
    main()
