"""Operations and bytes an algorithm needs, reckoned from shapes. Each
counts what is needed ONCE: recomputed work (remat, a backward kernel that
rebuilds the scores twice) earns no credit, so a share of a peak made from
these cannot pass 100% honestly."""
from __future__ import annotations

import numpy as np

_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def dense_params(m):
    """Parameters of a dense Llama/Mistral decoder with an untied head."""
    H, F, V, L = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                  m["num_hidden_layers"])
    kv = m["num_key_value_heads"] * (H // m["num_attention_heads"])
    per_layer = 2 * H * H + 2 * H * kv + 3 * H * F + 2 * H
    return L * per_layer + 2 * V * H + H


def matmul_params(m):
    """Parameters that take part in a matrix product for every token: all
    but the embedding table (a gather) and the norms."""
    H, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    return dense_params(m) - V * H - (2 * L + 1) * H


def attended_pairs(seq_len, doc_ids=None):
    """(query, key) pairs causal attention needs in one sequence, held
    inside documents where `doc_ids` (S,) is given."""
    if doc_ids is None:
        return seq_len * (seq_len + 1) // 2
    _, counts = np.unique(np.asarray(doc_ids), return_counts=True)
    return int(sum(int(n) * (int(n) + 1) // 2 for n in counts))


def attention_flops(m, pairs, backward=False):
    """QK^T and PV over `pairs` (query, key) pairs in every head of every
    layer: 4 * head_dim operations a pair forward, 10 backward (the scores
    rebuilt once, then dP, dV, dQ, dK)."""
    hd = m["hidden_size"] // m["num_attention_heads"]
    per_pair = (10 if backward else 4) * hd
    return per_pair * pairs * m["num_attention_heads"] * m["num_hidden_layers"]


def train_flops_per_step(m, tokens, pairs):
    """6 * matmul parameters * tokens, plus causal attention forward and
    backward. No credit for recomputation."""
    return 6 * matmul_params(m) * tokens + \
        attention_flops(m, pairs) + attention_flops(m, pairs, backward=True)


def kv_bytes_per_token(m, cache_dtype="bfloat16"):
    hd = m["hidden_size"] // m["num_attention_heads"]
    return 2 * m["num_hidden_layers"] * m["num_key_value_heads"] * hd * \
        _BYTES[cache_dtype]


def serve_step_bytes(m, live_tokens, weights_dtype="bfloat16",
                     cache_dtype="bfloat16"):
    """Bytes one serving step has to read: every weight that enters a
    matrix product once, and the keys and values of the live context."""
    return matmul_params(m) * _BYTES[weights_dtype] + \
        live_tokens * kv_bytes_per_token(m, cache_dtype)
