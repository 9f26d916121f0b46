"""Batched serving example: greedy generation on the shared runtime.

    PYTHONPATH=src python examples/serve_batch.py --arch gemma3-4b
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.launch.serve import Request, ServeLoop
from repro.models import model
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduce_config(get_config(args.arch))
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    loop = ServeLoop(cfg, params, batch=args.batch, max_len=64)

    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=args.new_tokens)
        for _ in range(6)
    ]
    loop.run(reqs, progress=lambda live, queued: print(
        f"  decode step: {live} live, {queued} queued"))
    for i, r in enumerate(reqs):
        print(f"request {i}: generated {len(r.generated)} tokens: {r.generated[:8]}...")


if __name__ == "__main__":
    main()
