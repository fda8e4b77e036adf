"""End-to-end LM training driver: dense decoder with VQ-Attention (the
paper's technique on the token graph) vs exact attention, on the synthetic
token stream, with checkpoints and restart.

Default is CPU-sized; pass --preset 100m for the ~100M-parameter run
(use a TPU host or be patient):

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""
import argparse

from repro.configs.base import ArchConfig
from repro import hostenv
from repro.train.loop import train

PRESETS = {
    "tiny": ArchConfig(name="tiny-lm", family="dense", n_layers=4,
                       d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
                       vocab=2048, remat=False, dtype="float32"),
    "100m": ArchConfig(name="lm-100m", family="dense", n_layers=12,
                       d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
                       vocab=32768, remat=True, dtype="float32"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vq", action="store_true",
                    help="enable VQ-Attention (codebook context)")
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()
    hostenv.enable_compile_cache()

    cfg = PRESETS[args.preset]
    if args.vq:
        cfg = cfg.with_vq(k=64, window=64)
    n_params = cfg.param_count()
    print(f"arch {cfg.name}: ~{n_params/1e6:.1f}M params, "
          f"vq_attn={cfg.vq_attn}")

    out = train(cfg, steps=args.steps, batch=args.batch, seq_len=args.seq,
                lr=3e-4, ckpt_dir=args.ckpt, ckpt_every=50, log_every=10)
    for h in out["history"]:
        print(f"  step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"({h['time']:.0f}s)")
    first, last = out["history"][0], out["history"][-1]
    print(f"\nloss: {first['loss']:.3f} -> {last['loss']:.3f} "
          f"({args.steps} steps, ckpts in {args.ckpt})")


if __name__ == "__main__":
    main()
