"""Train a small qwen3-family LM end to end on synthetic data: data pipeline
with prefetch, AdamW + cosine schedule, checkpoint/restart, and optional int8
gradient compression.  (~20M params by default so a few hundred steps run on
CPU; pass --full100m for a ~100M-param config if you have the patience.)

Run:  PYTHONPATH=src python examples/train_small_lm.py --steps 200
"""
import argparse
import dataclasses

from repro.configs import get_config
from repro.launch.train import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--full100m", action="store_true")
    args = ap.parse_args()

    cfg = get_config("qwen3-14b-smoke")
    if args.full100m:
        cfg = dataclasses.replace(cfg, name="qwen3-100m", n_layers=8,
                                  d_model=512, n_heads=8, n_kv_heads=4,
                                  head_dim=64, d_ff=1536, vocab_size=50304)
    else:
        cfg = dataclasses.replace(cfg, n_layers=6, d_model=256, n_heads=8,
                                  n_kv_heads=4, head_dim=32, d_ff=512,
                                  vocab_size=2048)
    print(f"training {cfg.name}: {cfg.n_params()/1e6:.1f}M params "
          f"(analytic), {args.steps} steps @ batch {args.batch} x seq {args.seq}")
    _, _, losses = train_loop(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 4, 10),
        lr=args.lr, compress_grads=args.compress_grads, log_every=20)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(ckpts in {args.ckpt_dir}; rerun to resume)")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
