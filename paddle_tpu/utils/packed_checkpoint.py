"""Packed single-file checkpoints via the C++ packer (libptckpt).

Replaces the reference's save_combine/load_combine C++ ops: every tensor
in one file with an index footer; the C++ writer thread overlaps disk
writes with the device→host transfer of the next tensor, and commit is
atomic (tmp + fsync + rename). Tree structure / dtypes / shapes live in
a `__meta__` JSON entry, so a checkpoint is exactly one file.

    save_packed("ckpt.pt", {"model": model.state_dict(), "step": 12})
    state = load_packed("ckpt.pt")
"""
from __future__ import annotations

import ctypes
import json
import os
import subprocess

import numpy as np

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "csrc")
_LIB = None


def _load_lib():
    global _LIB
    if _LIB is not None:
        return _LIB
    so = os.path.join(_CSRC, "libptckpt.so")
    # make every time: the rule depends on ptckpt.cpp, so a fresh .so
    # is a no-op and what loads is always built from the committed source
    subprocess.run(["make", "-C", _CSRC, "libptckpt.so"], check=True,
                   capture_output=True)
    lib = ctypes.CDLL(so)
    lib.ptckpt_writer_open.restype = ctypes.c_void_p
    lib.ptckpt_writer_open.argtypes = [ctypes.c_char_p]
    lib.ptckpt_write.restype = ctypes.c_int
    lib.ptckpt_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_char_p, ctypes.c_int64]
    lib.ptckpt_writer_close.restype = ctypes.c_int
    lib.ptckpt_writer_close.argtypes = [ctypes.c_void_p]
    lib.ptckpt_reader_open.restype = ctypes.c_void_p
    lib.ptckpt_reader_open.argtypes = [ctypes.c_char_p]
    lib.ptckpt_num_entries.restype = ctypes.c_int64
    lib.ptckpt_num_entries.argtypes = [ctypes.c_void_p]
    lib.ptckpt_entry_size.restype = ctypes.c_int64
    lib.ptckpt_entry_size.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptckpt_read.restype = ctypes.c_int64
    lib.ptckpt_read.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_char_p, ctypes.c_int64]
    lib.ptckpt_reader_close.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return lib


_SEP = "/"  # tree separator: state_dict keys contain dots, never slashes


def _flatten(tree, prefix=""):
    """dict-tree of arrays/scalars → {slash_path: leaf}."""
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, prefix + str(k) + _SEP))
    else:
        out[prefix[:-1]] = tree
    return out


def _unflatten(flat):
    root = {}
    for name, v in flat.items():
        parts = name.split(_SEP)
        d = root
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return root


def save_packed(path, tree):
    """tree: nested dict of arrays (jax/numpy/Tensor) and scalars."""
    from .._core.tensor import Tensor
    lib = _load_lib()
    flat = _flatten(tree)
    meta = {}
    h = lib.ptckpt_writer_open(path.encode())
    if not h:
        raise OSError(f"ptckpt: cannot open {path}")
    try:
        for name, v in flat.items():
            if isinstance(v, Tensor):
                v = np.asarray(v._value)
            if isinstance(v, (int, float, bool, str)) or v is None:
                meta[name] = {"kind": "scalar", "value": v}
                continue
            arr = np.ascontiguousarray(np.asarray(v))
            meta[name] = {"kind": "array", "dtype": str(arr.dtype),
                          "shape": list(arr.shape)}
            buf = arr.tobytes()
            if lib.ptckpt_write(h, name.encode(), buf, len(buf)) != 0:
                raise OSError("ptckpt: write failed")
        mbuf = json.dumps(meta).encode()
        if lib.ptckpt_write(h, b"__meta__", mbuf, len(mbuf)) != 0:
            raise OSError("ptckpt: meta write failed")
    finally:
        rc = lib.ptckpt_writer_close(h)
    if rc != 0:
        raise OSError(f"ptckpt: commit failed for {path}")


def load_packed(path):
    lib = _load_lib()
    h = lib.ptckpt_reader_open(path.encode())
    if not h:
        raise OSError(f"ptckpt: cannot open {path}")
    try:
        msize = lib.ptckpt_entry_size(h, b"__meta__")
        if msize < 0:
            raise OSError("ptckpt: missing __meta__")
        mbuf = ctypes.create_string_buffer(msize)
        lib.ptckpt_read(h, b"__meta__", mbuf, msize)
        meta = json.loads(mbuf.raw[:msize].decode())
        flat = {}
        for name, m in meta.items():
            if m["kind"] == "scalar":
                flat[name] = m["value"]
            else:
                n = lib.ptckpt_entry_size(h, name.encode())
                buf = ctypes.create_string_buffer(max(n, 1))
                got = lib.ptckpt_read(h, name.encode(), buf, n)
                if got != n:
                    raise OSError(f"ptckpt: short read for {name}")
                flat[name] = np.frombuffer(
                    buf.raw[:n], dtype=np.dtype(m["dtype"])).reshape(
                    m["shape"]).copy()
        return _unflatten(flat)
    finally:
        lib.ptckpt_reader_close(h)
