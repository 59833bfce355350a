"""Batched serving demo: prefill + decode with KV caches.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch llama3-8b]

Loads a (smoke-sized) model, submits a ragged batch of prompts, and
generates greedily + at temperature through the ServeEngine — the same
decode_step the decode_32k / long_500k dry-run cells lower at scale.
"""

import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import ARCHS, SKIP_CELLS, get_config
from repro.models import transformer as tf
from repro.serving import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=[a for a in ARCHS
                             if "decode_32k" not in SKIP_CELLS.get(a, set())])
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch, smoke=True)
    params = tf.init_params(cfg, jax.random.key(0))
    engine = ServeEngine(cfg, params, s_max=128)

    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (5, 9, 3, 7)]
    print(f"arch={cfg.name}: serving {len(prompts)} ragged prompts, "
          f"max_new={args.max_new}")

    t0 = time.time()
    res = engine.generate(prompts, max_new=args.max_new)
    dt = time.time() - t0
    toks = sum(len(o) - len(p) for o, p in zip(res.tokens, prompts))
    print(f"greedy: {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. prefill+compile)")
    for p, o in zip(prompts, res.tokens):
        print(f"  prompt[{len(p)}] → {o[len(p):][:10]}...")

    res_t = engine.generate(prompts, max_new=args.max_new, temperature=0.8,
                            seed=3)
    diff = sum(a != b for a, b in zip(res.tokens[0], res_t.tokens[0]))
    print(f"temperature=0.8 differs from greedy at {diff} positions "
          "(sampling live)")


if __name__ == "__main__":
    main()
