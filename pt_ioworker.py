"""Standalone DataLoader worker-process module — numpy only.

Lives OUTSIDE the paddle_tpu package on purpose: spawn workers resolve
their target function by module path, and importing anything under
`paddle_tpu.*` would execute the package __init__ (jax import + backend
config). A chip belongs to one process: a worker that initialised jax
on a TPU host would fail or hang against the parent that holds the
chip, so data workers must never touch jax at all.
Reference parity: the worker side of
python/paddle/io/dataloader/dataloader_iter.py:368
(_DataLoaderIterMultiProcess) — decode + collate off the parent's GIL.
"""
import traceback

import numpy as np


def default_collate(batch):
    """numpy-only clone of paddle_tpu.io.dataloader.default_collate_fn
    (Tensor branches omitted: process workers exchange numpy)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate([b[k] for b in batch]) for k in sample}
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return type(sample)(default_collate(list(col)) for col in transposed)
    return batch


def worker_main(task_q, res_q, dataset, collate, wid, nw, worker_init_fn,
                seed):
    """Worker-process loop: pull (seq, indices), decode, collate, push."""
    np.random.seed(seed + wid)
    if collate is None:
        collate = default_collate
    if worker_init_fn is not None:
        worker_init_fn(wid)
    while True:
        item = task_q.get()
        if item is None:
            break
        seq, indices = item
        try:
            batch = collate([dataset[i] for i in indices])
        except Exception as e:  # must cross the pickle boundary
            batch = RuntimeError(
                f"DataLoader worker raised {type(e).__name__}: {e}\n"
                + traceback.format_exc())
        res_q.put((seq, batch))
