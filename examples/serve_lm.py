"""Batched serving driver: continuous-batching engine over a reduced LM.

The TPU analogue of the paper's deployment loop (DMA-FIFO in, classify,
GPIO out): requests stream in, slots refill without draining the batch.

    PYTHONPATH=src python examples/serve_lm.py --arch granite-3-2b \
        --requests 10 --batch 4
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core import runtime
from repro.models import model as M
from repro.serving.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()
    runtime.init_compile_cache()

    cfg = get_config(args.arch).smoke()
    model = M.build(cfg)
    params, _ = model.init(jax.random.key(0))
    eng = Engine(cfg, params, batch_size=args.batch, max_len=64)

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(1, cfg.vocab, size=6).astype(np.int32),
                    max_new_tokens=args.max_new) for i in range(args.requests)]
    t0 = time.perf_counter()
    done = eng.submit_and_run(reqs)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in done)
    for r in done[:5]:
        print(f"req {r.uid}: prompt={list(r.prompt)} -> {r.out}")
    print(f"{len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s on this host)")


if __name__ == "__main__":
    main()
