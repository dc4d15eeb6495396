"""Packed ragged-batch attention with memory_efficient_attention.

    python examples/ragged_attention.py

Shows: documents of different lengths packed into ONE attention call
through the xformers-style BlockDiagonalCausalMask — the bias TYPE
routes to the varlen segment-id pallas kernel (no padding, no O(S^2)
mask), and split() recovers the per-document outputs. This is the
eager/offline face of the same masking the serving engine runs
compiled (reference: python/paddle/incubate/nn/
memory_efficient_attention.py).
"""
from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
_os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import paddle_tpu as pt
from paddle_tpu.incubate.nn.attn_bias import BlockDiagonalMask
from paddle_tpu.incubate.nn.memory_efficient_attention import (
    memory_efficient_attention,
)


def main():
    pt.seed(0)
    h, d = 4, 32
    # three "documents" with very different lengths — a ragged batch
    docs = [pt.randn([1, n, h, d]) for n in (37, 128, 9)]

    # pack them once; the mask carries the boundaries
    mask, packed = BlockDiagonalMask.from_tensor_list(docs)
    causal = mask.make_causal()

    out = memory_efficient_attention(packed, packed, packed,
                                     attn_bias=causal)
    outs = mask.split(out)
    for i, (doc, o) in enumerate(zip(docs, outs)):
        print(f"doc {i}: in {list(doc.shape)} -> out {list(o.shape)}")

    # proof of isolation: a document attending alone gives the SAME
    # output as inside the packed batch (no cross-document leakage)
    solo_mask = BlockDiagonalMask.from_seqlens([docs[0].shape[1]])
    solo = memory_efficient_attention(docs[0], docs[0], docs[0],
                                      attn_bias=solo_mask.make_causal())
    err = float(np.abs(outs[0].numpy() - solo.numpy()).max())
    print(f"packed-vs-solo max err: {err:.2e} (isolation holds)")
    assert err < 1e-4


if __name__ == "__main__":
    main()
