"""End-to-end driver: train a small LM for a few hundred steps, then serve
batched requests through the SMOL-pipelined serving engine (the paper is
an inference paper, so serving is the end-to-end deliverable).

    PYTHONPATH=src python examples/serve_llm.py [--steps 200]
"""

import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data.pipeline import PrefetchIterator, ShardedBatchSource, synthetic_lm_batch_fn
from repro.models.config import ModelConfig
from repro.serving import tokenizer as tok
from repro.serving.engine import Request, ServingEngine
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import TrainConfig, train


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    cfg = ModelConfig(
        "serve-demo", "dense", num_layers=4, d_model=128, num_heads=8,
        num_kv_heads=4, head_dim=16, d_ff=256, vocab_size=tok.VOCAB,
        qk_norm=True, dtype="float32",
    )
    print(f"model: {cfg.name}, ~{sum(np.prod(s) for s in [(cfg.padded_vocab_size, cfg.d_model)]) / 1e6:.1f}M embed params")

    # --- train on the synthetic bigram stream ---------------------------
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=3e-3),
                       warmup_steps=20, total_steps=args.steps)
    src = ShardedBatchSource(synthetic_lm_batch_fn(cfg.vocab_size, 16, 64), seed=0)
    it = PrefetchIterator(src)
    try:
        state, hist = train(cfg, tcfg, it, num_steps=args.steps, log_every=50)
    finally:
        it.close()
    print(f"trained {len(hist)} steps: loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")

    # --- serve batched requests through the pipelined engine ------------
    engine = ServingEngine(state["params"], cfg, batch_slots=4, max_len=96,
                           num_workers=2)
    reqs = [
        Request(uid=i, text=f"the quick brown fox {i} ", max_new_tokens=12)
        for i in range(args.requests)
    ]
    done, stats = engine.serve(reqs)
    print(f"\nserved {stats.completed} requests | {stats.tokens_generated} tokens "
          f"in {stats.wall_seconds:.2f}s ({stats.tokens_per_second:.1f} tok/s, "
          f"{stats.decode_steps} decode steps)")
    for r in done[:3]:
        ttft = r.first_token_at - r.submitted_at if (r.first_token_at and r.submitted_at) else None
        ttft_s = "n/a" if ttft is None else f"{ttft:.3f}s"
        print(f"  req {r.uid}: ttft {ttft_s}, output ids {r.output_ids[:8]}...")


if __name__ == "__main__":
    main()
