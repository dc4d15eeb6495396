"""Byte-level BPE tokenizer: Python trainer + C++ encode core (libpttext).

The reference ships its tokenizer hot loop in C++ (fast_tokenizer); ours
does the same through ctypes — vocab building, file formats, and training
stay in Python, while encode/decode run in native code. A pure-Python
encoder is kept both as the fallback (no compiler) and as the reference
for tests (C++ must match it exactly).

Format: GPT-2-style byte-level BPE without the unicode remap — tokens are
raw byte strings, merges ranked by training order.
"""
from __future__ import annotations

import collections
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
    so = os.path.join(_CSRC, "libpttext.so")
    # make every time: the rule depends on pttext.cpp, so a fresh .so
    # is a no-op and what loads is always built from the committed source
    subprocess.run(["make", "-C", _CSRC, "libpttext.so"], check=True,
                   capture_output=True)
    lib = ctypes.CDLL(so)
    lib.pttok_create.restype = ctypes.c_void_p
    lib.pttok_destroy.argtypes = [ctypes.c_void_p]
    lib.pttok_add_token.restype = ctypes.c_int
    lib.pttok_add_token.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_int64, ctypes.c_int32]
    lib.pttok_add_merge.restype = ctypes.c_int
    lib.pttok_add_merge.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                    ctypes.c_int32, ctypes.c_int32,
                                    ctypes.c_int32]
    lib.pttok_finalize.argtypes = [ctypes.c_void_p]
    lib.pttok_encode.restype = ctypes.c_int64
    lib.pttok_encode.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_int64,
                                 ctypes.POINTER(ctypes.c_int32),
                                 ctypes.c_int64]
    lib.pttok_decode.restype = ctypes.c_int64
    lib.pttok_decode.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_int32),
                                 ctypes.c_int64, ctypes.c_char_p,
                                 ctypes.c_int64]
    _LIB = lib
    return lib


def train_bpe(texts, vocab_size, specials=("<pad>", "<bos>", "<eos>")):
    """Train byte-level BPE. Returns (vocab: id->bytes, merges: list of
    (left_id, right_id, merged_id))."""
    vocab = {i: bytes([i]) for i in range(256)}
    merges = []
    corpus = [list(t.encode("utf-8")) for t in texts if t]
    next_id = 256
    target = vocab_size - len(specials)
    while next_id < target:
        counts = collections.Counter()
        for seq in corpus:
            counts.update(zip(seq, seq[1:]))
        if not counts:
            break
        (a, b), freq = counts.most_common(1)[0]
        if freq < 2:
            break
        vocab[next_id] = vocab[a] + vocab[b]
        merges.append((a, b, next_id))
        new_corpus = []
        for seq in corpus:
            out, i = [], 0
            while i < len(seq):
                if i + 1 < len(seq) and seq[i] == a and seq[i + 1] == b:
                    out.append(next_id)
                    i += 2
                else:
                    out.append(seq[i])
                    i += 1
            new_corpus.append(out)
        corpus = new_corpus
        next_id += 1
    return vocab, merges


class BPETokenizer:
    """Byte-level BPE with native encode core.

    Construct via `train()`, `from_files()`, or `__init__(vocab, merges)`.
    """

    def __init__(self, vocab, merges, specials=("<pad>", "<bos>", "<eos>"),
                 use_native=True):
        self.vocab = dict(vocab)                   # id -> bytes
        self.merges = list(merges)                 # (left, right, merged)
        self.specials = list(specials)
        base = max(self.vocab) + 1
        self.special_ids = {s: base + i for i, s in enumerate(self.specials)}
        for s, i in self.special_ids.items():
            self.vocab[i] = s.encode("utf-8")
        self.pad_token_id = self.special_ids.get("<pad>")
        self.bos_token_id = self.special_ids.get("<bos>")
        self.eos_token_id = self.special_ids.get("<eos>")
        self.vocab_size = max(self.vocab) + 1
        self._ranks = {(a, b): (r, m) for r, (a, b, m) in enumerate(self.merges)}
        self._native = None
        if use_native:
            try:
                self._native = self._build_native()
            except Exception:
                self._native = None

    # -- construction -----------------------------------------------------
    @classmethod
    def train(cls, texts, vocab_size, **kw):
        vocab, merges = train_bpe(texts, vocab_size,
                                  kw.get("specials", ("<pad>", "<bos>",
                                                      "<eos>")))
        return cls(vocab, merges, **kw)

    def save(self, path):
        data = {
            "vocab": {str(i): v.hex() for i, v in self.vocab.items()
                      if i not in self.special_ids.values()},
            "merges": self.merges,
            "specials": self.specials,
        }
        with open(path, "w") as f:
            json.dump(data, f)

    @classmethod
    def from_files(cls, path, **kw):
        with open(path) as f:
            data = json.load(f)
        vocab = {int(i): bytes.fromhex(v) for i, v in data["vocab"].items()}
        merges = [tuple(m) for m in data["merges"]]
        return cls(vocab, merges, specials=tuple(data["specials"]), **kw)

    def _build_native(self):
        lib = _load_lib()
        h = lib.pttok_create()
        for i, v in self.vocab.items():
            if i in self.special_ids.values():
                continue
            lib.pttok_add_token(h, v, len(v), i)
        for rank, (a, b, m) in enumerate(self.merges):
            lib.pttok_add_merge(h, a, b, m, rank)
        lib.pttok_finalize(h)
        return h

    def __del__(self):
        if getattr(self, "_native", None) is not None and _LIB is not None:
            try:
                _LIB.pttok_destroy(self._native)
            except Exception:
                pass

    # -- encode/decode ----------------------------------------------------
    def _encode_python(self, data: bytes):
        seq = list(data)
        while len(seq) > 1:
            best, best_pos = None, -1
            for i in range(len(seq) - 1):
                rm = self._ranks.get((seq[i], seq[i + 1]))
                if rm is not None and (best is None or rm[0] < best[0]):
                    best, best_pos = rm, i
            if best is None:
                break
            seq[best_pos:best_pos + 2] = [best[1]]
        return seq

    def encode(self, text, add_bos=False, add_eos=False):
        data = text.encode("utf-8")
        if self._native is not None:
            lib = _load_lib()
            out = (ctypes.c_int32 * max(len(data), 1))()
            n = lib.pttok_encode(self._native, data, len(data), out, len(data))
            if n < 0:
                raise RuntimeError(f"pttok_encode failed: {n}")
            ids = list(out[:n])
        else:
            ids = self._encode_python(data)
        if add_bos:
            ids = [self.bos_token_id] + ids
        if add_eos:
            ids = ids + [self.eos_token_id]
        return ids

    def decode(self, ids):
        ids = [int(i) for i in ids if int(i) not in self.special_ids.values()]
        if self._native is not None and ids:
            lib = _load_lib()
            arr = (ctypes.c_int32 * len(ids))(*ids)
            cap = sum(len(self.vocab[i]) for i in ids) + 1
            buf = ctypes.create_string_buffer(cap)
            n = lib.pttok_decode(self._native, arr, len(ids),
                                 ctypes.cast(buf, ctypes.c_char_p), cap)
            if n < 0:
                raise RuntimeError(f"pttok_decode failed: {n}")
            return buf.raw[:n].decode("utf-8", errors="replace")
        return b"".join(self.vocab[i] for i in ids).decode(
            "utf-8", errors="replace")

    def __call__(self, texts, max_length=None, padding=False):
        if isinstance(texts, str):
            texts = [texts]
        encoded = [self.encode(t) for t in texts]
        if max_length:
            encoded = [e[:max_length] for e in encoded]
        if padding:
            longest = max_length or max(len(e) for e in encoded)
            input_ids = np.full((len(encoded), longest), self.pad_token_id,
                                np.int64)
            mask = np.zeros((len(encoded), longest), np.int64)
            for i, e in enumerate(encoded):
                input_ids[i, :len(e)] = e
                mask[i, :len(e)] = 1
            return {"input_ids": input_ids, "attention_mask": mask}
        return {"input_ids": [np.asarray(e, np.int64) for e in encoded]}
