"""End-to-end LM training driver: any assigned arch at reduced scale, with
deterministic data, cosine schedule, async checkpointing and auto-resume.

    PYTHONPATH=src python examples/train_lm.py --arch granite-3-2b --steps 200
    PYTHONPATH=src python examples/train_lm.py --arch granite-3-2b \
        --preset 100m --steps 300         # ~100M-param variant (slow on CPU)

Kill it mid-run and start again: it resumes from the last checkpoint.
"""
import argparse
import dataclasses

import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import runtime
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--preset", choices=["smoke", "100m"], default="smoke")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()
    runtime.init_compile_cache()

    cfg = get_config(args.arch).smoke()
    if args.preset == "100m":
        cfg = dataclasses.replace(
            cfg, n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
            head_dim=64, d_ff=2048, vocab=32768, micro_batch=args.batch,
            dtype=jnp.float32, param_dtype=jnp.float32)
    t = Trainer(cfg, TrainerConfig(
        total_steps=args.steps, seq_len=args.seq_len, global_batch=args.batch,
        lr=3e-3, warmup_steps=max(5, args.steps // 20),
        ckpt_dir=args.ckpt_dir, ckpt_every=25, log_every=10))

    def log(step, m):
        extra = " STRAGGLER" if m.get("straggler") else ""
        print(f"step {step:5d}  loss {m['loss']:.4f}  gnorm {m['grad_norm']:.3f}{extra}",
              flush=True)

    state, history = t.run(on_metrics=log)
    print(f"final loss: {history[-1]:.4f} (first: {history[0]:.4f})")


if __name__ == "__main__":
    main()
