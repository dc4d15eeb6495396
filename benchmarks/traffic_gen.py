"""One general generator for every traffic file under traffic/.

Stratified draws: a span that offers N requests takes, for the k-th prompt
length, output length and inter-arrival gap, the distribution's quantile at
(k + 0.5) / N, the gaps rescaled to fill the span exactly. Their order and
pairing come from the traffic file's own `order_seed`; `--seed` makes the
token ids (and the weights). So every run of a cell offers the same requests
of the same lengths at the same times, and only their contents differ by
seed. Measured on the chip (PR 24): with the order drawn from `--seed`, six
seeds spread the TTFT median over 3.6-7.0 s, because with nine requests in a
window which prompts collide decides it, while one seed six times stayed
within 2%. Due times are fixed before the run starts and never depend on
completions (open loop).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

_SEED_MASK = (1 << 63) - 1


def _rng(seed, stream):
    return np.random.default_rng([int(seed) & _SEED_MASK, stream])


def _ndtri(p):
    from scipy.special import ndtri
    return ndtri(p)


def length_quantiles(n, spec):
    """n stratified integer lengths of a clipped lognormal."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"length distribution {spec['dist']!r}: want lognormal")
    p = (np.arange(n) + 0.5) / n
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * _ndtri(p))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gap_quantiles(n, span_s, dist="exponential"):
    """n stratified inter-arrival gaps that sum to span_s exactly."""
    if n == 0:
        return np.zeros((0,))
    p = (np.arange(n) + 0.5) / n
    if dist == "exponential":
        g = -np.log1p(-p)
    elif dist == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"arrival distribution {dist!r}")
    return g * (span_s / g.sum())


def _block_order(rng, n, block):
    """A seeded order of range(n) in which every run of `block` consecutive
    items is itself a stratified sample: item k of the sorted list goes to
    block k % n_blocks, and the seed permutes inside and across blocks."""
    if not block or block >= n:
        return rng.permutation(n)
    n_blocks = -(-n // block)
    blocks = [rng.permutation(np.arange(b, n, n_blocks)) for b in range(n_blocks)]
    return np.concatenate([blocks[i] for i in rng.permutation(n_blocks)])


@dataclasses.dataclass
class Offered:
    """One request as the generator offers it."""
    idx: int
    span: str               # "ramp" | "window" | "backlog"
    due_s: float            # seconds after the generator starts
    prompt: list
    n_out: int


def _span(rng, ids, name, n, start_s, span_s, spec, vocab, first_idx):
    block = spec.get("block")
    plen = length_quantiles(n, spec["prompt"])[_block_order(rng, n, block)]
    olen = length_quantiles(n, spec["output"])[_block_order(rng, n, block)]
    if spec["kind"] == "backlog":
        due = np.full(n, start_s)
    else:
        gaps = gap_quantiles(n, span_s, spec["arrivals"]["dist"])
        # the median gap is split between the head and the tail of the
        # span, so every request is due strictly inside it; the others, in
        # seeded order, lie between the requests: the same multiset every run
        edge, rest = (gaps[n // 2], np.delete(gaps, n // 2)) if n else (0.0, gaps)
        rest = rest[rng.permutation(len(rest))]
        due = start_s + edge / 2 + np.concatenate([[0.0], np.cumsum(rest)])[:n]
    return [Offered(first_idx + k, name, float(due[k]),
                    ids.integers(1, vocab, int(plen[k])).tolist(), int(olen[k]))
            for k in range(n)]


def serving_traffic(spec, seed, seconds, vocab):
    """-> (ramp_s, [Offered...] sorted by due time). The ramp and the window
    are two spans drawn apart; a run shorter than ramp_s ramps for
    `seconds`, so short checking runs stay short."""
    rng, ids = _rng(spec["order_seed"], 1), _rng(seed, 3)
    ramp_s = float(min(spec["ramp_s"], seconds))
    if spec["kind"] == "backlog":
        out = _span(rng, ids, "backlog", int(spec["backlog_requests"]), 0.0, 0.0,
                    spec, vocab, 0)
        return ramp_s, out
    if spec["kind"] != "open_loop":
        raise ValueError(f"traffic kind {spec['kind']!r}")
    rate = float(spec["rate_rps"])
    n_ramp = int(round(rate * ramp_s))
    n_win = int(round(rate * seconds))
    out = _span(rng, ids, "ramp", n_ramp, 0.0, ramp_s, spec, vocab, 0)
    out += _span(rng, ids, "window", n_win, ramp_s, float(seconds), spec, vocab, n_ramp)
    return ramp_s, out


def packed_batches(spec, seed, n_batches, vocab):
    """Training batches of `batch` sequences of `seq_len` tokens, packed from
    seeded documents of heavy-tailed (lognormal, clipped) length. -> list of
    (ids, labels, doc_ids) int32 arrays; labels are next tokens, -1 where the
    next token belongs to another document or lies past the sequence. With
    "packed": false the sequences are single documents (plain causal) and
    the batch is (ids, labels)."""
    rng = _rng(seed, 2)
    B, S = int(spec["batch"]), int(spec["seq_len"])
    d = spec["documents"]
    out = []
    for _ in range(n_batches):
        toks = rng.integers(1, vocab, (B, S + 1), dtype=np.int64).astype(np.int32)
        ids, nxt = toks[:, :-1], toks[:, 1:].copy()
        if not spec.get("packed", True):
            out.append((ids, nxt))
            continue
        doc = np.zeros((B, S), np.int32)
        for b in range(B):
            pos = k = 0
            while pos < S:
                n = int(np.clip(np.rint(rng.lognormal(math.log(d["median"]), d["sigma"])),
                                d["min"], d["max"]))
                doc[b, pos:pos + n] = k
                pos, k = pos + n, k + 1
        last = np.ones((B, S), bool)
        last[:, :-1] = doc[:, 1:] != doc[:, :-1]
        nxt[last] = -1
        out.append((ids, nxt, doc))
    return out
