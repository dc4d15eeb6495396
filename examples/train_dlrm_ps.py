"""DLRM CTR training over parameter-server sparse tables.

    python examples/train_dlrm_ps.py                 # in-process shards
    python examples/train_dlrm_ps.py --sockets       # real TCP PS tier
    python examples/train_dlrm_ps.py --cpp           # native C++ shards

Shows: host-RAM SparseTable shards (per-row adagrad), the
DistributedEmbedding pull/push flow around a jitted dense tower, the
same run over the socket tier the multi-process deployment uses, and
the libptps native backend (docs/distributed.md § Parameter-server
mode).
"""
from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

_os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import argparse
import time

import numpy as np

from paddle_tpu.distributed import ps
from paddle_tpu.models.dlrm import DLRMConfig, DLRMTrainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sockets", action="store_true",
                    help="run the shards behind the real TCP PS tier")
    ap.add_argument("--cpp", action="store_true",
                    help="native C++ shards (csrc/ptps.cpp) instead of "
                         "the Python tier (implies --sockets)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--shards", type=int, default=2)
    args = ap.parse_args()

    cfg = DLRMConfig(emb_dim=16, n_sparse=8, dense_dim=13,
                     bottom=(64, 32), top=(64, 32))
    spec = dict(optimizer="adagrad", lr=0.05)   # one spec, all backends

    def mk_table(s):
        return ps.SparseTable(cfg.emb_dim, seed=s, **spec)

    servers = []
    if args.cpp:
        for s in range(args.shards):
            servers.append(ps.CppPSServer(cfg.emb_dim, seed=s, **spec))
    elif args.sockets:
        for s in range(args.shards):
            srv = ps.EmbeddingPSServer([mk_table(s)])
            srv.serve_in_thread()
            servers.append(srv)
    if servers:
        _os.environ["PT_PS_ENDPOINTS"] = ",".join(s.endpoint
                                                  for s in servers)
        client = ps.init_worker()
        print(f"PS tier: {len(servers)} "
              f"{'native C++' if args.cpp else 'python'} socket servers "
              f"({_os.environ['PT_PS_ENDPOINTS']})")
    else:
        client = ps.PSClient([mk_table(s) for s in range(args.shards)])

    tr = DLRMTrainer(cfg, client, seed=0, lr=0.05)
    rng = np.random.RandomState(0)

    def batch():
        ids = rng.randint(0, 100_000, (args.batch, cfg.n_sparse))
        ids = ids.astype(np.int64) \
            + np.arange(cfg.n_sparse, dtype=np.int64)[None] * 1_000_003
        dense = rng.randn(args.batch, cfg.dense_dim).astype(np.float32)
        y = ((dense[:, 0] + (ids[:, 0] % 2) * 1.5 - 0.7) > 0)
        return ids, dense, y.astype(np.float32)

    t0 = time.perf_counter()
    for it in range(args.steps):
        loss = tr.train_step(*batch())
        if it % 10 == 0 or it == args.steps - 1:
            print(f"step {it:3d}  loss {loss:.4f}  "
                  f"rows materialized {len(client)}")
    dt = time.perf_counter() - t0
    print(f"{args.steps * args.batch / dt:.0f} examples/s "
          f"(PS round-trip included)")

    if servers:
        ps.stop_worker()
        for s in servers:
            s.close()


if __name__ == "__main__":
    main()
