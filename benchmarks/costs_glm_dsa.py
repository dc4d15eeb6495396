"""Parameters, operations and bytes of the `glm_dsa` family, reckoned from
ITS shapes (`costs.py` counts a dense decoder, `costs_laguna.py` Laguna's;
both stay as they are). Each counts what MUST be read or computed ONCE: the
index keys a row scores, read once a run; the latent rows a selection
KEEPS, never the context the kernel walks to find them; the experts held
that got a row; each weight once. So a share of a peak made from these
cannot pass 100% honestly."""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def sparse_layers(m):
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def attention_params(m):
    H, nh, qr, rank = (m["hidden_size"], m["num_attention_heads"],
                       m["q_lora_rank"], m["kv_lora_rank"])
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return (H * qr + qr + qr * nh * (nope + rope) + H * (rank + rope) + rank
            + rank * nh * (nope + vd) + nh * vd * H)


def indexer_params(m):
    ih, idim = m["index_n_heads"], m["index_head_dim"]
    return (m["q_lora_rank"] * ih * idim + m["hidden_size"] * idim + 2 * idim
            + m["hidden_size"] * ih)


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params(m, sparse, experts):
    """One layer with `experts` routed experts (a dense layer has none)."""
    H = m["hidden_size"]
    n = attention_params(m) + indexer_params(m) + 2 * H
    if not sparse:
        return n + 3 * H * m["intermediate_size"]
    router = m.get("router_experts", m["n_routed_experts"])
    return (n + H * router + router + expert_params(m) * m["n_shared_experts"]
            + experts * expert_params(m))


def params_held(m):
    dense = m["first_k_dense_replace"]
    return (dense * layer_params(m, False, 0)
            + sparse_layers(m) * layer_params(m, True, m["n_routed_experts"])
            + 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def params_published(pub):
    """The whole model without its multi-token head."""
    return params_held(dict(pub, router_experts=pub["n_routed_experts"]))


def cache_bytes_per_token(m, precision):
    """The latent row and the index key of one token in every layer held."""
    return m["num_hidden_layers"] * _BYTES[precision["kv_cache"]] * (
        m["kv_lora_rank"] + m["qk_rope_head_dim"] + m["index_head_dim"])


def index_needed(m, precision, context_tokens, run_tokens, rows):
    """-> (bytes, operations) ONE layer's index scores need for one step:
    `context_tokens` (row, column) pairs at 2 x heads x head_dim operations
    each; the index keys of `run_tokens` columns read once (a run's rows
    share theirs: the sum over slots of the longest context, which the
    engine books as `pt_ragged_kv_tokens`); the rows' queries and weights
    read and one float32 score a pair written."""
    ih, idim = m["index_n_heads"], m["index_head_dim"]
    return (run_tokens * idim * _BYTES[precision["kv_cache"]]
            + rows * ih * (idim * _BYTES[precision["weights"]] + 4)
            + context_tokens * 4,
            context_tokens * 2 * ih * idim)


def latent_attn_needed(m, precision, selected_tokens, rows):
    """-> (bytes, operations) ONE layer's attention over the SELECTED rows
    needs for one step: each kept (row, position) pair fetches one latent
    row (rank + rope values) and costs every head a product over the whole
    row for its score and over `rank` for its value; the rows' absorbed
    queries read and latent outputs written."""
    nh, rank = m["num_attention_heads"], m["kv_lora_rank"]
    width = rank + m["qk_rope_head_dim"]
    wb = _BYTES[precision["weights"]]
    return (selected_tokens * width * _BYTES[precision["kv_cache"]]
            + rows * nh * (width + rank) * wb,
            selected_tokens * nh * 2 * (width + rank))


def moe_needed(m, precision, experts_touched, assignments):
    """-> (bytes, operations) ONE sparse layer's routed products need for
    one step: the weights of every HELD expert that got a row, once; each
    assignment's row in and out; 2 operations a weight and assignment."""
    wb = _BYTES[precision["weights"]]
    return (experts_touched * expert_params(m) * wb
            + assignments * 2 * m["hidden_size"] * wb,
            assignments * 2 * expert_params(m))


def matmul_params_outside_experts(m):
    """Parameters that enter a matrix product for every row: attention,
    indexer, the dense layers' SwiGLU, router and shared experts of the
    sparse layers, the head (the embedding is a gather)."""
    H = m["hidden_size"]
    n = H * m["vocab_size"]
    for li in range(m["num_hidden_layers"]):
        sparse = li >= m["first_k_dense_replace"]
        n += layer_params(m, sparse, 0) - 2 * H
    return n


def serve_step_needed(m, precision, rows, experts_touched, assignments,
                      context_tokens, run_tokens, selected_tokens):
    """-> (bytes, operations) one serving step needs: every weight outside
    the routed experts once and 2 operations a row for it; the held experts
    that got a row; and, summed over the layers, the index scores and the
    attention over the selected rows (counts a LAYER, as the engine books
    them; experts summed over the sparse layers)."""
    wb = _BYTES[precision["weights"]]
    L = m["num_hidden_layers"]
    ib, io = index_needed(m, precision, context_tokens, run_tokens, rows)
    ab, ao = latent_attn_needed(m, precision, selected_tokens, rows)
    eb, eo = moe_needed(m, precision, experts_touched, assignments)
    dense = matmul_params_outside_experts(m)
    return (dense * wb + eb + L * (ib + ab),
            rows * 2 * dense + eo + L * (io + ao))
