"""The paper's train->extract->quantize->bake flow applied to an LM — the
generalization of smallNet's deployment to the transformer zoo.

    PYTHONPATH=src python examples/quantize_deploy.py --arch granite-3-2b
"""
import argparse

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core import ptq, runtime
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.serving.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--train-steps", type=int, default=30)
    args = ap.parse_args()
    runtime.init_compile_cache()

    cfg = get_config(args.arch).smoke()
    print(f"== 1. train {args.arch} (reduced) for {args.train_steps} steps ==")
    t = Trainer(cfg, TrainerConfig(total_steps=args.train_steps, seq_len=64,
                                   global_batch=8, lr=3e-3, warmup_steps=5))
    state, history = t.run()
    print(f"   loss {history[0]:.3f} -> {history[-1]:.3f}")

    print("== 2. post-training int8 quantization (per-channel, symmetric) ==")
    qparams = ptq.quantize_tree(state["params"])
    errs = ptq.quantization_error(state["params"], qparams)
    worst = max(errs.items(), key=lambda kv: kv[1])
    print(f"   quantized {len(errs)} weight tensors; worst rel-L2 err "
          f"{worst[1]:.4f} at {worst[0]}")
    deq = ptq.dequantize_tree(qparams)

    print("== 3. serve float vs int8-deployed, compare generations ==")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=6).astype(np.int32) for _ in range(4)]
    out_f = Engine(cfg, state["params"], batch_size=2, max_len=32).submit_and_run(
        [Request(i, p.copy(), 6) for i, p in enumerate(prompts)])
    out_q = Engine(cfg, deq, batch_size=2, max_len=32).submit_and_run(
        [Request(i, p.copy(), 6) for i, p in enumerate(prompts)])
    agree = np.mean([a == b for r1, r2 in zip(out_f, out_q)
                     for a, b in zip(r1.out, r2.out)])
    print(f"   greedy-token agreement float vs int8: {agree*100:.0f}%")
    int8_bytes = sum(l.q.size for l in jax.tree_util.tree_leaves(
        qparams, is_leaf=lambda x: isinstance(x, ptq.QuantTensor))
        if isinstance(l, ptq.QuantTensor))
    f32_bytes = sum(l.size * 4 for l in jax.tree_util.tree_leaves(state["params"]))
    print(f"   weight bytes: {f32_bytes} f32 -> ~{int8_bytes} int8 "
          f"({f32_bytes/int8_bytes:.1f}x smaller)")


if __name__ == "__main__":
    main()
