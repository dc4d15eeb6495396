"""ResNet training two ways: paddle-style eager and compiled Trainer.

    python examples/train_resnet.py --arch resnet18 --mode trainer
"""
from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

_os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import argparse
import time

import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.parallel import create_mesh, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--mode", choices=["eager", "trainer"], default="trainer")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--img", type=int, default=64)
    args = ap.parse_args()

    net = getattr(pt.vision.models, args.arch)(num_classes=10)
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=net.parameters(),
                                weight_decay=1e-4)
    rng = np.random.RandomState(0)

    def batch():
        x = rng.randn(args.batch, 3, args.img, args.img).astype(np.float32)
        y = rng.randint(0, 10, args.batch)
        return x, y

    if args.mode == "eager":
        lossf = pt.nn.CrossEntropyLoss()
        for step in range(args.steps):
            x, y = batch()
            loss = lossf(net(pt.to_tensor(x)), pt.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            print(f"step {step} loss {float(loss):.4f}")
        return

    mesh = create_mesh({"dp": -1})

    def loss_fn(model, data):
        x, y = data
        return pt.nn.functional.cross_entropy(model(x), y)

    tr = Trainer(net, opt, loss_fn, mesh=mesh,
                 batch_spec=(P("dp"), P("dp")))
    t0 = time.perf_counter()
    for step in range(args.steps):
        loss = tr.step(batch())
        print(f"step {step} loss {float(loss):.4f}")
    print(f"{args.steps / (time.perf_counter() - t0):.2f} steps/s")


if __name__ == "__main__":
    main()
