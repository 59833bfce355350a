"""End-to-end training driver: a small LM for a few hundred steps.

Run:  PYTHONPATH=src python examples/train_lm.py                # ~15M params
      PYTHONPATH=src python examples/train_lm.py --preset 100m  # real HW
      (re-run the same command after a kill: it resumes from the latest
       checkpoint automatically)

Exercises the full production loop on synthetic structured data:
deterministic sharded pipeline, AdamW + warmup-cosine, microbatch
accumulation, checkpoint/auto-resume, SIGTERM-safe preemption,
straggler flagging.
"""

import argparse
import dataclasses

from repro import compile_cache
from repro.configs import TrainConfig, get_config
from repro.data.pipeline import TokenPipeline
from repro.training import train

PRESETS = {
    # ~15M params: a few hundred steps in minutes on one CPU core
    "15m": dict(n_layers=4, d_model=256, n_heads=8, n_kv_heads=4,
                d_ff=1024, vocab_size=2048, batch=8, seq=256),
    # ~124M: the "train ~100M for a few hundred steps" configuration —
    # sized for a real accelerator, runs (slowly) on CPU too
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 d_ff=3072, vocab_size=32768, batch=32, seq=512),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="15m", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--workdir", default="/tmp/repro_train_lm")
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    compile_cache.enable()

    p = PRESETS[args.preset]
    cfg = dataclasses.replace(
        get_config("llama3-8b", smoke=True),  # llama-family block stack
        name=f"lm-{args.preset}",
        n_layers=p["n_layers"], d_model=p["d_model"], n_heads=p["n_heads"],
        n_kv_heads=p["n_kv_heads"], d_ff=p["d_ff"],
        vocab_size=p["vocab_size"], d_head=0,
        dtype="float32", param_dtype="float32",
    )
    print(f"model: {cfg.param_count() / 1e6:.1f}M params "
          f"({p['n_layers']}L × {p['d_model']}d, vocab {p['vocab_size']})")

    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=20,
                       total_steps=args.steps, microbatch=args.microbatch,
                       weight_decay=0.01)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, batch=p["batch"],
                         seq_len=p["seq"], seed=0)
    state, history = train(cfg, tcfg, pipe, workdir=args.workdir,
                           num_steps=args.steps, ckpt_every=50, log_every=10)
    first = sum(h["loss"] for h in history[:5]) / max(len(history[:5]), 1)
    last = sum(h["loss"] for h in history[-5:]) / max(len(history[-5:]), 1)
    print(f"loss: {first:.3f} → {last:.3f} over {len(history)} steps "
          f"({'LEARNING' if last < first else 'check config'})")


if __name__ == "__main__":
    main()
