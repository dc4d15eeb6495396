"""ERNIE finetune — paddle-style classification recipe on TPU.

    python examples/finetune_ernie.py --steps 30
    python examples/finetune_ernie.py --compiled   # jitted Trainer path

Shows: the ERNIE model family, a varlen token corpus packed through the
C++ libptio .ptvr pipeline, the legacy reader facade, and both the eager
tape loop and the compiled Trainer over the same model.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=48)
    ap.add_argument("--compiled", action="store_true",
                    help="use the jitted Trainer instead of the eager tape")
    args = ap.parse_args()

    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.ernie import (ErnieConfig,
                                         ErnieForSequenceClassification)
    from paddle_tpu.io import native

    pt.seed(0)
    cfg = ErnieConfig.tiny()
    model = ErnieForSequenceClassification(cfg, num_classes=2)
    opt = pt.optimizer.AdamW(learning_rate=5e-4,
                             parameters=model.parameters())
    ce = pt.nn.CrossEntropyLoss()

    # --- synthetic "sentiment" corpus: class k uses token band k --------
    rng = np.random.RandomState(0)
    seqs, labels = [], []
    for i in range(256):
        lab = i % 2
        lo, hi = (1, cfg.vocab_size // 2) if lab == 0 else \
            (cfg.vocab_size // 2, cfg.vocab_size)
        n = rng.randint(8, args.seq)
        seqs.append(rng.randint(lo, hi, n).astype(np.int32))
        labels.append(lab)

    # varlen corpus through the native C++ pipeline, padded per batch
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "corpus.ptvr")
        native.write_varlen_records(path, seqs)
        ds = native.VarlenRecordDataset(path)
        loader = native.NativeVarlenLoader(
            ds, batch_size=args.batch, shuffle=True, seed=1,
            decode=lambda b: np.frombuffer(b, np.int32))
        label_by_key = {s.tobytes(): l for s, l in zip(seqs, labels)}

        def batches():
            while True:
                for recs in loader:
                    # position 0 is a fixed [CLS]=0 anchor the pooler reads
                    ids = np.zeros((len(recs), args.seq), np.int64)
                    for j, r in enumerate(recs):
                        n = min(len(r), args.seq - 1)
                        ids[j, 1:1 + n] = r[:n]
                    ys = np.asarray([label_by_key[r.tobytes()]
                                     for r in recs])
                    yield ids, ys

        it = batches()
        if args.compiled:
            from jax.sharding import Mesh, PartitionSpec as P
            mesh = Mesh(np.asarray(jax.devices()), ("dp",))
            from paddle_tpu.parallel.trainer import Trainer
            tr = Trainer(model, opt, lambda m, b: ce(m(b[0]), b[1]),
                         mesh=mesh, batch_spec=(P("dp"), P("dp")))
            for step in range(args.steps):
                ids, ys = next(it)
                loss = tr.step((ids, ys))
                if step % 5 == 0 or step == args.steps - 1:
                    print(f"[trainer] step {step:3d} "
                          f"loss {float(np.asarray(loss)):.4f}")
            tr.sync_model()
        else:
            for step in range(args.steps):
                ids, ys = next(it)
                loss = ce(model(pt.to_tensor(ids)), pt.to_tensor(ys))
                loss.backward()
                opt.step()
                opt.clear_grad()
                if step % 5 == 0 or step == args.steps - 1:
                    print(f"[eager]   step {step:3d} "
                          f"loss {float(loss.numpy()):.4f}")

    # quick eval on fresh samples
    model.eval()
    ids = np.zeros((64, args.seq), np.int64)
    ys = np.zeros(64, np.int64)
    for i in range(64):
        lab = i % 2
        lo, hi = (1, cfg.vocab_size // 2) if lab == 0 else \
            (cfg.vocab_size // 2, cfg.vocab_size)
        n = rng.randint(8, args.seq - 1)
        ids[i, 1:1 + n] = rng.randint(lo, hi, n)
        ys[i] = lab
    pred = model(pt.to_tensor(ids)).numpy().argmax(-1)
    print(f"eval accuracy: {(pred == ys).mean():.2%}")


if __name__ == "__main__":
    main()
