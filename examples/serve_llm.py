"""Online LLM serving: continuous batching, int8 KV cache, speculative
decoding, chunked prefill.

    python examples/serve_llm.py                     # greedy, fp cache
    python examples/serve_llm.py --spec 4            # prompt-lookup spec
    python examples/serve_llm.py --cache int8
    python examples/serve_llm.py --spec 4 --chunked  # split-fuse prefill

Shows: ServingEngine admission/eviction over the paged KV pool,
per-request sampling params, and the r4 serving features — all
token-exact vs plain greedy decode (docs/serving.md).
"""
from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

_os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import argparse
import time

import numpy as np

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.models.llama_serving import Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", type=int, default=0,
                    help="speculative chunk width G (0 = plain decode)")
    ap.add_argument("--chunked", action="store_true",
                    help="chunked prefill (needs --spec >= 2)")
    ap.add_argument("--cache", choices=["fp", "int8"], default="fp")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--logprobs", action="store_true",
                    help="record per-token raw-model logprobs")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree (0 = single device); "
                         "shards weights + KV pool over a tp mesh")
    args = ap.parse_args()

    cfg = LlamaConfig.tiny(vocab=512, hidden=128, layers=2, heads=8,
                           kv_heads=4, ffn=256, seq=256)
    params = M.init_params(cfg, seed=0)
    mesh = None
    if args.tp > 1:
        import jax
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:args.tp]).reshape(args.tp),
                    ("tp",))
    eng = ServingEngine(
        params, cfg, max_seqs=4, max_seq_len=256, page_size=16,
        cache_dtype="int8" if args.cache == "int8" else None,
        spec_decode=args.spec, chunked_prefill=args.chunked, mesh=mesh)

    rng = np.random.RandomState(0)
    for i in range(args.requests):
        prompt = list(rng.randint(1, cfg.vocab_size,
                                  int(rng.randint(8, 48))))
        # mix greedy and sampled requests in one batch
        kw = {} if i % 3 else {"temperature": 0.8, "top_k": 16, "seed": i}
        eng.submit(Request(f"req{i}", prompt,
                           max_new_tokens=args.new_tokens,
                           logprobs=args.logprobs, **kw))

    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    total = sum(len(r.output) for r in done)
    print(f"{len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s), {eng.device_steps} device steps")
    if args.spec > 1:
        rate = eng.spec_accepted / max(eng.spec_drafted, 1)
        print(f"speculative: {eng.spec_drafted} drafted, "
              f"{eng.spec_accepted} accepted ({rate:.0%})")
    for r in done[:3]:
        print(f"  {r.rid}: {r.output[:10]}{'...' if len(r.output) > 10 else ''}")
        if r.logprobs is not None:
            print(f"    logprobs: {[round(x, 3) for x in r.logprobs[:6]]}...")


if __name__ == "__main__":
    main()
