"""End-to-end training driver.

On this CPU container it trains reduced configs (see
examples/train_moe_100m.py for the ~100M driver); on a real pod the
same entry point jits ``build_train_step`` onto the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
      --reduced --steps 100 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compress-bits", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config, reduced_config
    from repro.launch.compile_cache import configure_compile_cache
    from repro.data import DataConfig
    from repro.training import TrainConfig, train

    configure_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = cfg.replace(dtype="float32")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed)
    tcfg = TrainConfig(lr=args.lr, total_steps=args.steps,
                       warmup=max(5, args.steps // 20),
                       ckpt_dir=args.ckpt_dir,
                       grad_compress_bits=args.grad_compress_bits,
                       log_every=max(1, args.steps // 20))

    def log(step, metrics):
        print(f"step {step:5d}  loss {metrics['loss']:.4f}  "
              f"ce {metrics['ce']:.4f}  gnorm {metrics['grad_norm']:.2f}")

    res = train(cfg, dcfg, tcfg, seed=args.seed, hooks=log)
    print(f"done: {res.final_step} steps in {res.wall_time:.1f}s "
          f"(resumed_from={res.resumed_from})")


if __name__ == "__main__":
    main()
