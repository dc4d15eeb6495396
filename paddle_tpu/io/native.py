"""ctypes bindings for libptio (C++ data-pipeline core) + RecordFile
dataset/loader.

The native path covers the byte-level hot loop (mmap read, shuffle,
batch memcpy, prefetch) that the reference does in
paddle/fluid/operators/reader; Python only sees finished batches.
Builds lazily on first use (`make -C paddle_tpu/csrc`).
"""
from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_LIB = None
_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "csrc")


def _load():
    global _LIB
    if _LIB is not None:
        return _LIB
    so = os.path.join(_CSRC, "libptio.so")
    # make every time: the rule depends on ptio.cpp, so a fresh .so is
    # a no-op and what loads is always built from the committed source
    subprocess.run(["make", "-C", _CSRC, "libptio.so"], check=True,
                   capture_output=True)
    lib = ctypes.CDLL(so)
    lib.ptio_open_records.restype = ctypes.c_void_p
    lib.ptio_open_records.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.ptio_num_records.restype = ctypes.c_int64
    lib.ptio_num_records.argtypes = [ctypes.c_void_p]
    lib.ptio_close_records.argtypes = [ctypes.c_void_p]
    lib.ptio_pipeline_create.restype = ctypes.c_void_p
    lib.ptio_pipeline_create.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         ctypes.c_int, ctypes.c_int,
                                         ctypes.c_uint64, ctypes.c_int64]
    lib.ptio_pipeline_start_epoch.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                              ctypes.c_int]
    lib.ptio_pipeline_num_batches.restype = ctypes.c_int64
    lib.ptio_pipeline_num_batches.argtypes = [ctypes.c_void_p]
    lib.ptio_pipeline_next.restype = ctypes.c_int64
    lib.ptio_pipeline_next.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint8)]
    lib.ptio_pipeline_destroy.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return lib


def available():
    try:
        _load()
        return True
    except Exception:
        return False


def write_record_file(path, array):
    """Serialize a (N, ...) array as fixed-size raw records."""
    arr = np.ascontiguousarray(array)
    arr.tofile(path)
    return arr.shape, arr.dtype


class RecordFileDataset:
    """Fixed-record binary dataset backed by mmap (native)."""

    def __init__(self, path, record_shape, dtype):
        self.record_shape = tuple(record_shape)
        self.dtype = np.dtype(dtype)
        self.record_bytes = int(np.prod(self.record_shape)) * self.dtype.itemsize
        lib = _load()
        self._h = lib.ptio_open_records(str(path).encode(), self.record_bytes)
        if not self._h:
            raise IOError(f"cannot open record file {path}")
        self._n = lib.ptio_num_records(self._h)

    def __len__(self):
        return self._n

    def close(self):
        if self._h:
            _load().ptio_close_records(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeDataLoader:
    """Multithreaded prefetching loader over a RecordFileDataset.

    Yields np arrays (batch, *record_shape); shuffle reshuffles per epoch
    in C++ (deterministic from seed+epoch).
    """

    def __init__(self, dataset: RecordFileDataset, batch_size=1, shuffle=False,
                 drop_last=True, seed=0, num_threads=2, capacity=8):
        self.ds = dataset
        self.batch_size = batch_size
        self.num_threads = num_threads
        lib = _load()
        self._p = lib.ptio_pipeline_create(dataset._h, batch_size,
                                           1 if shuffle else 0,
                                           1 if drop_last else 0, seed, capacity)
        self._epoch = 0
        self._buf = np.empty((batch_size,) + dataset.record_shape,
                             dtype=dataset.dtype)

    def __len__(self):
        # pure count (never touches epoch state — calling len() mid-
        # iteration must not restart the pipeline)
        return _load().ptio_pipeline_num_batches(self._p)

    def __iter__(self):
        lib = _load()
        lib.ptio_pipeline_start_epoch(self._p, self._epoch, self.num_threads)
        self._epoch += 1
        ptr = self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        while True:
            n = lib.ptio_pipeline_next(self._p, ptr)
            if n <= 0:
                break
            yield np.array(self._buf[:n], copy=True)

    def __del__(self):
        try:
            if self._p:
                _load().ptio_pipeline_destroy(self._p)
                self._p = None
        except Exception:
            pass


# ---------------------------------------------------------------- varlen
def _load_varlen():
    lib = _load()
    if getattr(lib, "_varlen_bound", False):
        return lib
    lib.ptio_open_varlen.restype = ctypes.c_void_p
    lib.ptio_open_varlen.argtypes = [ctypes.c_char_p]
    lib.ptio_varlen_num_records.restype = ctypes.c_int64
    lib.ptio_varlen_num_records.argtypes = [ctypes.c_void_p]
    lib.ptio_varlen_max_record.restype = ctypes.c_int64
    lib.ptio_varlen_max_record.argtypes = [ctypes.c_void_p]
    lib.ptio_close_varlen.argtypes = [ctypes.c_void_p]
    lib.ptio_varlen_pipeline_create.restype = ctypes.c_void_p
    lib.ptio_varlen_pipeline_create.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_uint64, ctypes.c_int64]
    lib.ptio_varlen_pipeline_start_epoch.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int]
    lib.ptio_varlen_pipeline_num_batches.restype = ctypes.c_int64
    lib.ptio_varlen_pipeline_num_batches.argtypes = [ctypes.c_void_p]
    lib.ptio_varlen_pipeline_next.restype = ctypes.c_int64
    lib.ptio_varlen_pipeline_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64)]
    lib.ptio_varlen_pipeline_destroy.argtypes = [ctypes.c_void_p]
    lib._varlen_bound = True
    return lib


def write_varlen_records(path, records):
    """Pack an iterable of bytes-like records into a .ptvr file
    ("PTVR" + u32 version + u64 n + u64 offsets[n+1] + blob)."""
    import struct
    blobs = [bytes(memoryview(np.ascontiguousarray(r)).cast("B"))
             if isinstance(r, np.ndarray) else bytes(r) for r in records]
    offs = [0]
    for b in blobs:
        offs.append(offs[-1] + len(b))
    with open(path, "wb") as f:
        f.write(b"PTVR")
        f.write(struct.pack("<I", 1))
        f.write(struct.pack("<Q", len(blobs)))
        f.write(np.asarray(offs, np.uint64).tobytes())
        for b in blobs:
            f.write(b)
    return len(blobs)


class VarlenRecordDataset:
    """Variable-length binary record dataset (native mmap; validated
    index — the serving/LLM token-sequence layout the fixed-record path
    can't express)."""

    def __init__(self, path):
        lib = _load_varlen()
        self._h = lib.ptio_open_varlen(str(path).encode())
        if not self._h:
            raise IOError(f"cannot open varlen record file {path} "
                          f"(missing, truncated, or corrupt index)")
        self._n = lib.ptio_varlen_num_records(self._h)
        self.max_record = lib.ptio_varlen_max_record(self._h)

    def __len__(self):
        return self._n

    def close(self):
        if self._h:
            _load_varlen().ptio_close_varlen(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeVarlenLoader:
    """Prefetching loader over variable-length records.

    Yields lists of uint8 arrays (one per record, exact sizes); pass
    `decode` (e.g. lambda b: np.frombuffer(b, np.int32)) to map bytes
    to samples in the worker-free consumer loop.
    """

    def __init__(self, dataset: VarlenRecordDataset, batch_size=1,
                 shuffle=False, drop_last=True, seed=0, num_threads=2,
                 capacity=8, decode=None):
        self.ds = dataset
        self.batch_size = batch_size
        self.num_threads = num_threads
        self.decode = decode
        lib = _load_varlen()
        self._p = lib.ptio_varlen_pipeline_create(
            dataset._h, batch_size, 1 if shuffle else 0,
            1 if drop_last else 0, seed, capacity)
        self._epoch = 0
        self._buf = np.empty(batch_size * max(int(dataset.max_record), 1),
                             np.uint8)
        self._sizes = np.empty(batch_size, np.int64)

    def __len__(self):
        # pure count (never touches epoch state)
        return _load_varlen().ptio_varlen_pipeline_num_batches(self._p)

    def __iter__(self):
        lib = _load_varlen()
        lib.ptio_varlen_pipeline_start_epoch(self._p, self._epoch,
                                             self.num_threads)
        self._epoch += 1
        bptr = self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        sptr = self._sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
        while True:
            n = lib.ptio_varlen_pipeline_next(self._p, bptr, sptr)
            if n <= 0:
                break
            out, off = [], 0
            for i in range(n):
                sz = int(self._sizes[i])
                rec = np.array(self._buf[off:off + sz], copy=True)
                off += sz
                out.append(self.decode(rec) if self.decode else rec)
            yield out

    def __del__(self):
        try:
            if self._p:
                _load_varlen().ptio_varlen_pipeline_destroy(self._p)
                self._p = None
        except Exception:
            pass
